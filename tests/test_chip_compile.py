"""The main-path Pallas kernels compile for a TPU v5e at full size.

Each case lowers and compiles one kernel for a *described* v5e chip (no
chip attached) at n = 2,097,152 rows, the size of ``chip_smoke.py``'s
``laplace3d(128)``: what Mosaic refuses here it would refuse on the chip,
and interpret-mode tests cannot see that.  ``sellcs_spmv_pallas`` is not
among them: Mosaic refuses its gather (``kernels.sellcs_spmv.NO_LOWERING``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_diag import block_diag_matmul_pallas
from repro.kernels.fused_update import fused_axpby_dots_pallas
from repro.kernels.tsmm import tsmm_pallas
from repro.kernels.tsmttsm import tsmttsm_pallas

N = 2_097_152
HBM_BYTES = 16e9          # one v5e chip
F32 = jnp.float32

#: name -> (kernel call, argument shapes); float32 throughout
CASES = {
    "tsmm_b8": (
        lambda V, X: tsmm_pallas(V, X, row_tile=512, interpret=False),
        ((N, 8), (8, 8))),
    "tsmttsm_b8": (
        lambda V, W: tsmttsm_pallas(V, W, row_tile=512, interpret=False),
        ((N, 8), (N, 8))),
    "tsmttsm_kahan_b8": (
        lambda V, W: tsmttsm_pallas(V, W, row_tile=512, kahan=True,
                                    interpret=False),
        ((N, 8), (N, 8))),
    "fused_axpby_dots_b1": (
        lambda x, y: fused_axpby_dots_pallas(x, y, 1.0, 2.0, dot_yy=True,
                                             dot_xy=True, row_tile=512,
                                             interpret=False),
        ((N, 1), (N, 1))),
    "block_diag_bs16": (
        lambda B, x: block_diag_matmul_pallas(B, x, row_tile=512,
                                              interpret=False),
        ((N // 16, 16, 16), (N, 1))),
}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the compile cache off (a
    compile for a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                           # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the Mosaic kernel
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
