"""Compile-only rehearsal of every Pallas kernel for a TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
v5e that is described, not attached.  Each case lowers one kernel at the
widths the served models use and asserts that Mosaic emitted the kernel
(``tpu_custom_call``).  This catches what interpret mode cannot: a kernel
body using a primitive Mosaic has no lowering for (a value-level
``dynamic_slice``, a gather), a misaligned block, too much VMEM.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library at a time, and every worker of a
parallel run imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (ff_attention, ff_elementwise, ff_fused, ff_guard,
                           ff_math, ff_matmul, ff_reduce)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _mean_sq_fused(x):
    # the generic fused-chain executor with a trailing row reduction (the
    # RMSNorm statistic under ff_reduce); picks Pallas by backend, which
    # the test steers to "tpu"
    from repro.ff import fusion
    return fusion.fused(lambda xf: (xf * xf).sum())(x)


# name -> (fn, operand shapes); widths from Granite-3.0-2B (d_model 2048,
# d_ff 8192, head_dim 64) and the fused-row limit
CASES = {
    "ff_softmax_logsumexp": (
        lambda x: ff_fused.ff_softmax(x, mode="logsumexp"), [(8, 16384)]),
    "ff_softmax_logsumexp_accurate": (
        lambda x: ff_fused.ff_softmax(x, mode="logsumexp", accurate=True),
        [(8, 16384)]),
    "ff_softmax": (lambda x: ff_fused.ff_softmax(x), [(8, 16384)]),
    "ff_softmax_accurate": (
        lambda x: ff_fused.ff_softmax(x, accurate=True), [(8, 16384)]),
    "ff_norm_stats": (ff_fused.ff_norm_stats, [(2048, 2048)]),
    "fused_rowsum_mean_sq": (_mean_sq_fused, [(2048, 2048)]),
    "ff_rowsum": (ff_reduce.ff_rowsum, [(256, 8192)]),
    "flash_attention_pallas": (
        ff_attention.flash_attention_pallas,
        [(1, 128, 4, 64), (1, 256, 2, 64), (1, 256, 2, 64)]),
    "ff_matmul_ozaki": (ff_matmul.ff_matmul_ozaki, [(8, 2048), (2048, 8192)]),
    "ff_matmul_hybrid": (ff_matmul.ff_matmul, [(256, 2048), (2048, 512)]),
    "ff_matmul_dot2": (ff_matmul.ff_matmul_dot2, [(128, 512), (512, 256)]),
    "guard_flags": (ff_guard.guard_flags, [(256, 2048), (256, 2048)]),
    "elementwise_mul22": (
        lambda *a: ff_elementwise.elementwise("mul22", *a), [(256, 2048)] * 4),
    "math_exp": (lambda h, l: ff_math.math_elementwise("exp", h, l),
                 [(256, 2048)] * 2),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, monkeypatch):
    from repro.ff import dispatch
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
