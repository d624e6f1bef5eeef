"""Pallas TPU kernel for compensated (FF) row reduction.

Reduces the last axis of a 2-D array into an FF pair per row using the
paper's TwoSum cascade (Sum3 quality), processing column-blocks streamed
through VMEM.  Used by the training substrate for loss/grad-norm/LN-stat
reductions when the precision policy requests ``ff_reductions``.

Grid: (rows/br, cols/bc) with the column dimension innermost; the running
(s, c, cc) cascade lives in VMEM scratch and persists across column steps.
Inside a block the reduction walks the lane-groups in order (the same
cascade as ``ff_fused``), so the order is deterministic (bit-reproducible
across shardings of other dims).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ff_fused import _fold_lanes, _lane_cascade

Array = jnp.ndarray


def _ff_rowsum_kernel(x_ref, oh_ref, ol_ref, s_acc, c_acc, cc_acc,
                      *, nc: int, lane: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        c_acc[...] = jnp.zeros_like(c_acc)
        cc_acc[...] = jnp.zeros_like(cc_acc)

    s, c, cc = _lane_cascade(x_ref[...], s_acc[...], c_acc[...],
                             cc_acc[...], lane)
    s_acc[...] = s
    c_acc[...] = c
    cc_acc[...] = cc

    @pl.when(j == nc - 1)
    def _flush():
        # fold the `lane` per-lane accumulators exactly, sequentially
        fh, fl = _fold_lanes(s_acc[...], c_acc[...], cc_acc[...])
        oh_ref[...] = fh
        ol_ref[...] = fl


@functools.partial(jax.jit, static_argnames=("br", "bc", "lane", "interpret"))
def ff_rowsum(x: Array, *, br: int = 256, bc: int = 512, lane: int = 128,
              interpret: bool = False) -> Tuple[Array, Array]:
    """Compensated row-sum: x(R, C) -> FF(R,).  Returns (hi, lo)."""
    x = jnp.asarray(x, jnp.float32)
    R, C = x.shape
    br = min(br, R)
    bc = min(bc, C)
    lane = min(lane, bc)
    bc -= bc % lane if bc % lane else 0
    bc = max(bc, lane)
    pr, pc = (-R) % br, (-C) % bc
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    Rp, Cp = x.shape
    nc = Cp // bc
    grid = (Rp // br, nc)
    out = jax.ShapeDtypeStruct((Rp, 1), jnp.float32)
    oh, ol = pl.pallas_call(
        functools.partial(_ff_rowsum_kernel, nc=nc, lane=lane),
        out_shape=(out, out),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=(
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((br, lane), jnp.float32),
            pltpu.VMEM((br, lane), jnp.float32),
            pltpu.VMEM((br, lane), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return oh[:R, 0], ol[:R, 0]
