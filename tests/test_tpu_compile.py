"""Compile-only checks of the main-path ACK kernels for a TPU v5e chip.

Each test compiles one tile kernel at the Flickr tile geometry the auto
partitioner picks (n1=4096 rows, ELL width 512, n2=128 lanes) for a
described — not attached — v5e chip, the way the chip's own compiler
would, and checks that the Pallas kernels lowered to Mosaic
(``tpu_custom_call``) and that no kernel needs more temporary memory
than the chip has.  Nothing runs; results are covered by the interpret-
mode sweeps in ``test_kernels.py``.

The topology is described inside a module-scoped fixture (never at
import): only one process at a time may load the TPU compiler library,
and collection happens in every test worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ack
from repro.kernels import ops

N1, W, N2 = 4096, 512, 128            # Flickr at scale 1.0, auto geometry
HBM_BYTES = 16 * 10**9                # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """Compiles for a described chip cannot be read back without one, so
    keep them out of JAX's persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _shapes(sharding):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return {
        "h": s((N1, N2)), "w": s((N2, N2)), "acc": s((N1, N2)),
        "cols": s((N1, W), jnp.int32), "vals": s((N1, W)),
        "mask": s((N1, W), jnp.bool_), "flag": s((N1,), jnp.bool_),
        "scores": s((N1, W)),
    }


KERNELS = {
    "gemm_xla": (ack._gemm_xla, ("h", "w", "acc"), False),
    "spdmm_xla_sum": (
        lambda h, c, v, m, a, f: ack._spdmm_xla(h, c, v, m, a, f, op="sum"),
        ("h", "cols", "vals", "mask", "acc", "flag"), False),
    "sddmm_pair_xla": (ack._sddmm_pair_xla,
                       ("h", "h", "cols", "mask", "scores"), False),
    "pallas_gemm": (lambda x, w: ops.gemm(x, w), ("h", "w"), True),
    "pallas_spdmm": (lambda c, v, h: ops.spdmm(c, v, h),
                     ("cols", "vals", "h"), True),
    "pallas_sddmm": (lambda hd, hs, c: ops.sddmm(hd, hs, c),
                     ("h", "h", "cols"), True),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, arg_names, is_pallas = KERNELS[name]
    shapes = _shapes(one_chip)
    compiled = jax.jit(fn).lower(*(shapes[a] for a in arg_names)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == is_pallas
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < HBM_BYTES, mem
