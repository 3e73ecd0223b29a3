import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the family that only the tests use is found through the benchmark's lookup
from perfbench.lib import spec  # noqa: E402

_TEST_FAMILIES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "families")
if _TEST_FAMILIES not in spec.FAMILY_DIRS:
    spec.FAMILY_DIRS.append(_TEST_FAMILIES)


@pytest.fixture(scope="session")
def serving_cells():
    """Every cell of BENCHMARK.json whose traffic ``kind`` is ``serve``, in
    the file's order: what a reader of the serving loop is listed for,
    however many cells later PRs append."""
    bench = spec.benchmark()
    return [w["name"] for w in bench["workloads"]
            if spec.cell(w["name"], bench)[2]["kind"] == "serve"]
