"""Kernels of the serving path compiled at their real widths for a described
TPU v5e, with no chip (the third rehearsal of the on-chip-measurement guide):
what Mosaic refuses — a block that does not fit VMEM, a slice off the tiling
— fails here and costs no chip time.  Nothing runs; a compile that passes is
no measurement.  All such tests live in this ONE file: the worker that runs
it loads the TPU's library, and the topology is described inside a fixture,
never while a module is imported."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.parallel import expert as X


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (experts held, model width, expert width): smallthinker-21b-a3b whole,
# openpangu-ultra-moe-718b's share
@pytest.mark.parametrize("held,d,f", [(64, 2560, 768), (16, 7680, 2048)])
def test_the_expert_tile_kernel_compiles_at_the_cells_widths(one_chip, held,
                                                            d, f):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    bf = jnp.bfloat16
    fn = jax.jit(lambda wg, wu, wd, x, e: X.tile_ffn(wg, wu, wd, x, e,
                                                     jax.nn.relu))
    # the kernel interprets itself on a CPU backend; here it must be Mosaic's
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = fn.lower(sds((held, d, f), bf), sds((held, d, f), bf),
                            sds((held, f, d), bf), sds((64, d), bf),
                            sds((), jnp.int32)).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the expert's matrices are read where they lie: no copy of one out of
    # the stack before the kernel
    assert f"bf16[{d},{f}]" not in text and f"bf16[1,{d},{f}]" not in text
