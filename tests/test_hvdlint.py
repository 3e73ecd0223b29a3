"""hvdlint is self-proving: every rule has a positive fixture (clean
code passes) and a negative fixture (the violation is caught, with the
right file/line), the pragma escape hatch works, and the REAL repo is
clean under the full rule set — so the linter can gate CI
(docs/static-analysis.md#hvdlint)."""

import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "scripts", "hvdlint.py")


@pytest.fixture(scope="module")
def lint():
    spec = importlib.util.spec_from_file_location("_hvdlint", LINT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_hvdlint"] = mod
    spec.loader.exec_module(mod)
    return mod


def _write(root, rel, text):
    path = root / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(text))
    return rel


# ------------------------------------------------------------ knob-registry
def _knob_fixture(tmp_path, code):
    _write(tmp_path, "horovod_tpu/common/knobs.py",
           "KNOBS = {'HOROVOD_GOOD': None, 'HOROVOD_GOOD_SUB': None}\n")
    _write(tmp_path, "docs/knobs.md",
           "| `HOROVOD_GOOD` | x |\n| `HOROVOD_GOOD_SUB` | x |\n")
    _write(tmp_path, "pkg/mod.py", code)
    return tmp_path


def test_knob_registry_clean(lint, tmp_path):
    root = _knob_fixture(tmp_path, """\
        import os
        V = os.environ.get("HOROVOD_GOOD")
        # prose glob: the HOROVOD_GOOD_* family
        """)
    assert lint.check_knob_registry(str(root), scan=["pkg"]) == []


def test_knob_registry_flags_unregistered(lint, tmp_path):
    root = _knob_fixture(tmp_path, """\
        import os
        V = os.environ.get("HOROVOD_EVIL")
        """)
    out = lint.check_knob_registry(str(root), scan=["pkg"])
    assert len(out) == 1 and "HOROVOD_EVIL" in out[0].message
    assert out[0].path == "pkg/mod.py" and out[0].line == 2


def test_knob_registry_flags_bad_glob_and_missing_doc(lint, tmp_path):
    root = _knob_fixture(tmp_path, "# the HOROVOD_NOPE_* knobs\n")
    _write(tmp_path, "docs/knobs.md", "| `HOROVOD_GOOD` | x |\n")
    out = lint.check_knob_registry(str(root), scan=["pkg"])
    msgs = " | ".join(v.message for v in out)
    assert "HOROVOD_NOPE_* matches no registered knob" in msgs
    assert "HOROVOD_GOOD_SUB has no docs/knobs.md row" in msgs


def test_knob_registry_pragma_allows(lint, tmp_path):
    root = _knob_fixture(
        tmp_path,
        'V = "HOROVOD_EVIL"  # hvdlint: allow[knob-registry]\n')
    assert lint.check_knob_registry(str(root), scan=["pkg"]) == []


# -------------------------------------------------------- metrics-documented
def _metrics_fixture(tmp_path, doc):
    _write(tmp_path, "m.py", """\
        class _R:
            def counter(self, name, help):
                return None
            gauge = histogram = counter
        REGISTRY = _R()
        A = REGISTRY.counter("hvd_x_hits_total", "h")
        B = REGISTRY.counter("hvd_x_misses_total", "h")
        C = REGISTRY.gauge("hvd_y_depth", "h")
        D = REGISTRY.histogram("hvd_z_seconds", "h")
        """)
    _write(tmp_path, "d.md", doc)
    return tmp_path


def test_metrics_documented_clean_with_shorthand(lint, tmp_path):
    root = _metrics_fixture(tmp_path, """\
        | `hvd_x_hits_total` / `_misses_total` | counter |
        | `hvd_y_depth{rank=...}` | gauge |
        | `hvd_z_seconds` | histogram |
        """)
    out = lint.check_metrics_documented(str(root), metrics_rel="m.py",
                                        docs_rel="d.md",
                                        lint_exposition=False)
    assert out == []


def test_metrics_documented_flags_missing_row(lint, tmp_path):
    root = _metrics_fixture(tmp_path,
                            "| `hvd_x_hits_total` |\n| `hvd_z_seconds` |\n")
    out = lint.check_metrics_documented(str(root), metrics_rel="m.py",
                                        docs_rel="d.md",
                                        lint_exposition=False)
    missing = {v.message.split()[2] for v in out}
    assert missing == {"hvd_x_misses_total", "hvd_y_depth"}


def test_metrics_doc_brace_alternation_expands(lint):
    names = lint._doc_metric_names(
        "| `hvd_perf_native_op_{us,bytes}_total{name=}` |")
    assert {"hvd_perf_native_op_us_total",
            "hvd_perf_native_op_bytes_total"} <= names


# --------------------------------------------------------- serve-determinism
_DET_SCOPES = {"s.py": ["Scheduler", "plan_fn"]}


def test_determinism_clean(lint, tmp_path):
    _write(tmp_path, "s.py", """\
        import time
        class Scheduler:
            def plan(self, reqs):
                for r in sorted(set(reqs)):
                    r.admitted_t = time.perf_counter()  # metering ok
                return list(reqs)
        def outside():
            # time control flow OUTSIDE the lockstep scopes is fine
            if time.time() > 0:
                return {1, 2}
        """)
    assert lint.check_serve_determinism(str(tmp_path),
                                        scopes=_DET_SCOPES) == []


def test_determinism_flags_rng_time_and_set_iteration(lint, tmp_path):
    _write(tmp_path, "s.py", """\
        import time, random
        class Scheduler:
            def plan(self, reqs):
                if time.monotonic() > self.deadline:
                    reqs = reqs[:1]
                random.shuffle(reqs)
                for r in set(reqs):
                    yield r
        """)
    out = lint.check_serve_determinism(str(tmp_path), scopes=_DET_SCOPES)
    msgs = " | ".join(v.message for v in out)
    assert "wall-clock value drives control flow" in msgs
    assert "RNG call" in msgs
    assert "iteration over an unordered set" in msgs
    assert "`random` imported" in msgs


# ----------------------------------------------------------- serve-kv-retry
def test_kv_retry_clean(lint, tmp_path):
    _write(tmp_path, "w.py", """\
        class F:
            def _kv_op(self, fn, what):
                return fn()
            def _kv_get(self, kv, scope, key):
                return self._kv_op(lambda: kv.get_kv(scope, key), "g")
            def _kv_put(self, kv, scope, key, v):
                self._kv_op(lambda: kv.put_kv(scope, key, v), "p")
        """)
    assert lint.check_serve_kv_retry(str(tmp_path), files=("w.py",)) == []


def test_kv_retry_flags_raw_call(lint, tmp_path):
    _write(tmp_path, "w.py", """\
        class F:
            def fetch(self, kv):
                return kv.get_kv("scope", "key")
        """)
    out = lint.check_serve_kv_retry(str(tmp_path), files=("w.py",))
    assert len(out) == 1 and "raw get_kv" in out[0].message
    assert out[0].line == 3


def test_kv_retry_covers_the_arrivals_readers_held_get(lint, tmp_path):
    """``wait_kv`` (http_client.KeyWaiter) is a KV leg like the others:
    clean as a thunk handed to ``_kv_op``, flagged raw — and the reader's
    own file is among those the rule reads."""
    _write(tmp_path, "a.py", """\
        class Arrivals:
            def _read(self):
                return self._kv_op(lambda: self._waiter.wait_kv("k", 1.0),
                                   "wait")
            def _raw(self):
                return self._waiter.wait_kv("k", 1.0)
        """)
    out = lint.check_serve_kv_retry(str(tmp_path), files=("a.py",))
    assert [(v.line, "raw wait_kv" in v.message) for v in out] == [(6, True)]
    import inspect
    files = inspect.signature(lint.check_serve_kv_retry).parameters["files"]
    assert "horovod_tpu/serve/arrivals.py" in files.default
    assert lint.check_serve_kv_retry() == []


# ----------------------------------------------------- unique-test-basenames
def test_basenames_clean(lint, tmp_path):
    _write(tmp_path, "tests/test_a.py", "")
    _write(tmp_path, "tests/conftest.py", "")
    _write(tmp_path, "tests/integration/test_a_integration.py", "")
    _write(tmp_path, "tests/integration/conftest.py", "")
    assert lint.check_unique_test_basenames(str(tmp_path)) == []


def test_basenames_flags_collision(lint, tmp_path):
    _write(tmp_path, "tests/test_a.py", "")
    _write(tmp_path, "tests/integration/test_a.py", "")
    out = lint.check_unique_test_basenames(str(tmp_path))
    assert len(out) == 1 and "import-file mismatch" in out[0].message


# ------------------------------------------------------------- signal-safety
def test_signal_safety_clean(lint, tmp_path):
    _write(tmp_path, "p.cc", """\
        // snprintf(would be bad) but comments are stripped
        static const char* kMsg = "printf(in a string is fine)";
        void PutStr(int fd, const char* s) {
          while (*s) { write(fd, s, strlen(s)); s += strlen(s); }
        }
        void Handler(int sig) {
          PutStr(2, kMsg);
          signal(sig, nullptr);
          raise(sig);
        }
        """)
    out = lint.check_signal_safety(
        str(tmp_path), rel="p.cc",
        allow=lint.SIGNAL_SAFE_CALLS | {"Handler"})
    assert out == []


def test_signal_safety_flags_unsafe_call(lint, tmp_path):
    _write(tmp_path, "p.cc", """\
        void Handler(int sig) {
          char buf[64];
          snprintf(buf, sizeof(buf), "%d", sig);
        }
        """)
    out = lint.check_signal_safety(
        str(tmp_path), rel="p.cc",
        allow=lint.SIGNAL_SAFE_CALLS | {"Handler"})
    assert len(out) == 1 and "snprintf" in out[0].message
    assert out[0].line == 3


def test_signal_safety_real_file_is_handler_safe(lint):
    """The real postmortem.cc passes with the DEFAULT allowlist — no
    fixture-only entries hiding a regression."""
    assert lint.check_signal_safety() == []


# --------------------------------------------------- scenario-determinism
def test_scenario_determinism_clean(lint, tmp_path):
    rel = _write(tmp_path, "pkg/trace.py", """\
        import hashlib
        def draw(seed):
            for k in sorted({"a", "b"}):
                seed = (seed * 31 + len(k)) & 0xFFFFFFFF
            return seed
        """)
    assert lint.check_scenario_determinism(str(tmp_path),
                                           files=(rel,)) == []


def test_scenario_determinism_flags_imports_hash_env(lint, tmp_path):
    rel = _write(tmp_path, "pkg/trace.py", """\
        import random, os
        import uuid
        def draw(reqs, deadline):
            import time
            if time.monotonic() > deadline:
                reqs = reqs[:1]
            random.shuffle(reqs)
            token = uuid.uuid4()
            bucket = hash(token) % 8
            shards = os.getenv("SHARDS")
            for r in set(reqs):
                yield r, bucket, shards
        """)
    out = lint.check_scenario_determinism(str(tmp_path), files=(rel,))
    msgs = " | ".join(v.message for v in out)
    assert "random imported in a scenario module" in msgs
    assert "uuid imported in a scenario module" in msgs
    assert "time imported in a scenario module" in msgs
    assert "RNG call" in msgs
    assert "wall-clock value drives control flow" in msgs
    assert "builtin hash()" in msgs
    assert "environment read" in msgs
    assert "iteration over an unordered set" in msgs


def test_scenario_determinism_pragma_allows(lint, tmp_path):
    rel = _write(tmp_path, "pkg/trace.py", """\
        import time  # hvdlint: allow[scenario-determinism] wall metering
        def wall():
            return time.perf_counter()
        """)
    assert lint.check_scenario_determinism(str(tmp_path),
                                           files=(rel,)) == []


def test_scenario_determinism_real_modules_clean(lint):
    """The real scenario package passes with the DEFAULT file list."""
    assert lint.check_scenario_determinism() == []


# --------------------------------------------------------- trace-context
def _trace_fixture(tmp_path, trace_src, site_src):
    trace_rel = _write(tmp_path, "pkg/trace.py", trace_src)
    site_rel = _write(tmp_path, "pkg/site.py", site_src)
    return trace_rel, site_rel


def test_trace_context_clean(lint, tmp_path):
    trace_rel, site_rel = _trace_fixture(
        tmp_path, """\
        def span_id(rid, hop):
            return f"{rid}/{hop}"
        """, """\
        from .trace import span_args, span_id
        def emit(tl, req, trace_span, server):
            args = span_args(req.trace, "PREFILL", rid=req.req_id)
            tl.record_span("serve", "PREFILL", 1.0, args=args)
            tl.record_span("serve", "DECODE", 1.0,
                           args=span_args(req.trace, "DECODE"))
            trace_span(server, "router", "ROUTE", 0.0, 0.0,
                       args={"rid": req.req_id})
            return span_id(req.req_id, "ROUTE")
        """)
    assert lint.check_trace_context(str(tmp_path), files=(site_rel,),
                                    trace_rel=trace_rel) == []


def test_trace_context_flags_impure_ids_and_bare_spans(lint, tmp_path):
    trace_rel, site_rel = _trace_fixture(
        tmp_path, """\
        import time, uuid
        def span_id(rid, hop):
            return hash((rid, hop, uuid.uuid4(), time.time()))
        """, """\
        import random, time
        from .trace import span_id
        def emit(tl, req, trace_span, server):
            tl.record_span("serve", "PREFILL", 1.0,
                           args={"phase": "PREFILL"})
            tl.record_span("serve", "DECODE", 1.0)
            trace_span(server, "router", "ROUTE", 0.0, 0.0,
                       args=req.whatever)
            return span_id(req.req_id, time.time())
        """)
    out = lint.check_trace_context(str(tmp_path), files=(site_rel,),
                                   trace_rel=trace_rel)
    msgs = " | ".join(v.message for v in out)
    assert "imported in the trace-id module" in msgs
    assert "builtin hash() in the trace-id module" in msgs
    assert "span_id minted from time.time()" in msgs
    assert msgs.count("without trace-context args") == 3


def test_trace_context_pragma_allows(lint, tmp_path):
    trace_rel, site_rel = _trace_fixture(
        tmp_path, """\
        def span_id(rid, hop):
            return f"{rid}/{hop}"
        """, """\
        def emit(tl):
            tl.record_span("serve", "X", 1.0)  # hvdlint: allow[trace-context]
        """)
    assert lint.check_trace_context(str(tmp_path), files=(site_rel,),
                                    trace_rel=trace_rel) == []


def test_trace_context_real_modules_clean(lint):
    """The real serve path passes with the DEFAULT file list."""
    assert lint.check_trace_context() == []


# ------------------------------------------------------------------- driver
def test_real_repo_is_clean(lint):
    """The whole repo under the full rule set: the acceptance invariant
    `python scripts/hvdlint.py` exits 0."""
    violations = lint.run()
    assert violations == [], "\n".join(v.render() for v in violations)


def test_cli_exit_codes(tmp_path):
    ok = subprocess.run([sys.executable, LINT], capture_output=True,
                        text=True, cwd=REPO)
    assert ok.returncode == 0, ok.stderr
    assert "hvdlint OK" in ok.stdout
    # nonzero on a negative fixture, driven through the CLI
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_a.py").write_text("")
    (tmp_path / "tests" / "sub").mkdir()
    (tmp_path / "tests" / "sub" / "test_a.py").write_text("")
    bad = subprocess.run(
        [sys.executable, LINT, "--rule", "unique-test-basenames",
         "--root", str(tmp_path)],
        capture_output=True, text=True)
    assert bad.returncode == 1
    assert "unique-test-basenames" in bad.stderr


def test_cli_list_names_every_rule(lint):
    out = subprocess.run([sys.executable, LINT, "--list"],
                         capture_output=True, text=True)
    for rule in lint.RULES:
        assert rule in out.stdout
