"""Fused FF flash attention: blockwise attention with *compensated online
softmax* (the ``ff.attention`` op's implementation tiers).

Attention's online softmax over thousands of keys is exactly the long
f32 reduction the paper emulates 44-bit arithmetic for: every term of the
numerator/denominator is an ``exp`` whose ~2^-24 builtin error — plus the
~sqrt(K)*2^-24 accumulation drift — lands directly in the output weights.
The accurate class here runs the whole online recurrence in FF:

  * scores are FF (2^-44 class): ``q.k^T`` as TwoProd-exact products
    through a compensated Neumaier sum over the head dim, scaled with
    ``Mul212`` — an f32 dot product's ~2^-21 score error would be
    amplified straight into relative weight error by ``exp``;
  * the running-max shift ``s - m`` is an ``Add212`` on the FF scores
    (the shift itself needs no precision — any shared shift is
    mathematically exact in the softmax quotient; only the *applied*
    subtraction must keep the FF bits, and Add212 does);
  * exponentials are FF (``ffmath.exp22`` on the FF argument), so each
    term is 2^-44-class;
  * the rescale factor ``alpha = exp(m_old - m_new)`` is FF on an exact
    TwoSum argument;
  * numerator and denominator are FF accumulators: per kv-block sums run
    a lane-parallel Neumaier cascade (numerator terms are
    TwoProd-exact ``p_hi * v`` products with the ``p_lo * v`` residual
    folded into the compensation stream), and cross-block combining is
    ``Mul22``/``Add22`` — the TwoSum-carried recurrence of the tentpole;
  * the final normalize is ``Div22``.

Tiers (registered in ``repro.ff.dispatch`` as the ``attention`` op):

  fast   — the f32 online softmax previously inlined in
           ``repro.models.layers.flash_attention``, moved here verbatim so
           the registry default is trivially bitwise with the pre-registry
           model hot path.
  ff     — the compensated recurrence above in pure jnp (barrier-carrying
           core EFTs); the portable accurate class.
  pallas — the same algorithm as ONE Pallas kernel per (head, q-block)
           stripe: grid (B*H, n_q, n_kv) with the FF accumulators living
           in VMEM scratch across the innermost kv dimension (compiled on
           TPU, interpret-mode elsewhere).
  f64    — materialized-score native-f64 softmax attention (CPU accurate
           tier at hardware speed, and the test oracle).

This module is self-contained (no ``repro.models`` imports): the model
layers call it THROUGH the registry (``ff.attention``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import compensated, ffmath
from repro.core import ff as core_ff
from repro.core import transforms as T
from repro.core.ff import FF
from repro.kernels import eft
from repro.kernels.ff_elementwise import LANE, SUBLANE, _round_up
from repro.kernels.ff_fused import _fold_lanes, _lane_cascade

Array = jnp.ndarray

NEG_INF = -1e30


def _dims(q: Array, k: Array) -> Tuple[int, int, int, int, int, int]:
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"num_heads {H} not a multiple of kv heads {KV}")
    return B, Sq, H, hd, Skv, KV


def _resolve_scale(scale: Optional[float], hd: int) -> float:
    return (1.0 / math.sqrt(hd)) if scale is None else float(scale)


# ===========================================================================
# fast tier: the f32 online softmax (ex-``models.layers.flash_attention``)
# ===========================================================================

def flash_attention_fast(q: Array, k: Array, v: Array, *, causal: bool = True,
                         block_q: int = 128, block_kv: int = 128,
                         q_offset=0, kv_len: Optional[Array] = None,
                         scale: Optional[float] = None,
                         return_ff: bool = False):
    """Online-softmax blockwise attention, f32 accumulators (fast class).

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd); H = KV * G (GQA).
    Never materializes (Sq, Skv); peak extra memory is
    (B, KV, G, block_q, block_kv).  q_offset: absolute position of q[0]
    (for cached decode/prefill continuation).  ``kv_len``: optional (B,)
    per-row valid-key counts (ragged batches — the serving engine's mixed
    cache lengths); None keeps the static-Skv mask and is bitwise the
    pre-registry model path.  ``scale``: score scale, default
    ``1/sqrt(hd)``.
    """
    B, Sq, H, hd, Skv, KV = _dims(q, k)
    G = H // KV
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    pq, pkv = (-Sq) % bq, (-Skv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    nq, nkv = q.shape[1] // bq, k.shape[1] // bkv
    sc = _resolve_scale(scale, hd)

    # (nq, B, KV, G, bq, hd)
    qb = q.reshape(B, nq, bq, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(B, nkv, bkv, KV, hd).transpose(1, 0, 3, 2, 4)  # (nkv,B,KV,bkv,hd)
    vb = v.reshape(B, nkv, bkv, KV, hd).transpose(1, 0, 3, 2, 4)

    q_pos_base = jnp.asarray(q_offset, jnp.int32)

    def one_q_block(iq, qi):
        # qi: (B, KV, G, bq, hd)
        qi32 = qi.astype(jnp.float32) * sc
        q_pos = q_pos_base + iq * bq + jnp.arange(bq, dtype=jnp.int32)

        def kv_step(carry, jk):
            m, l, acc = carry
            kj = kb[jk].astype(jnp.float32)   # (B,KV,bkv,hd)
            vj = vb[jk].astype(jnp.float32)
            s = jnp.einsum("bkgqd,bksd->bkgqs", qi32, kj)   # (B,KV,G,bq,bkv)
            kv_pos = jk * bkv + jnp.arange(bkv, dtype=jnp.int32)
            mask = kv_pos[None, :] <= q_pos[:, None] if causal else \
                jnp.ones((bq, bkv), bool)
            # mask out kv padding
            mask = jnp.logical_and(mask, (kv_pos < Skv)[None, :])
            if kv_len is None:
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            else:
                rag = kv_pos[None, :] < kv_len[:, None]          # (B, bkv)
                full = jnp.logical_and(mask[None, None, None],
                                       rag[:, None, None, None])
                s = jnp.where(full, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bksd->bkgqd", p, vj)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  jnp.arange(nkv, dtype=jnp.int32))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B,KV,G,bq,hd)

    outs = lax.map(lambda args: one_q_block(*args),
                   (jnp.arange(nq, dtype=jnp.int32), qb))
    # (nq,B,KV,G,bq,hd) -> (B, Sq, H, hd)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * bq, H, hd)
    out = out[:, :Sq]
    if return_ff:
        return FF(out.astype(jnp.float32), jnp.zeros_like(out, jnp.float32))
    return out.astype(q.dtype)


# ===========================================================================
# ff tier: the compensated online recurrence in jnp (accurate class)
# ===========================================================================

def _ff_safe_den(den: FF) -> FF:
    """Guard a fully-masked row's zero denominator (mirrors the fast
    tier's ``max(l, 1e-30)``) without perturbing real denominators."""
    tiny = jnp.float32(1e-30)
    ok = den.hi > tiny
    return FF(jnp.where(ok, den.hi, tiny),
              jnp.where(ok, den.lo, jnp.float32(0.0)))


def flash_attention_ff(q: Array, k: Array, v: Array, *, causal: bool = True,
                       block_q: int = 32, block_kv: int = 128,
                       q_offset=0, kv_len: Optional[Array] = None,
                       scale: Optional[float] = None,
                       block: int = 128, return_ff: bool = False):
    """Compensated online-softmax attention (accurate class, pure jnp).

    Same blocked structure as the fast tier; scores AND the recurrence
    are FF (see module docstring).  Per kv-block sums go through the
    compensated blocked cascade (``ff_sum_blocked``); numerator terms are
    TwoProd-exact ``p_hi * v`` with the ``p_lo * v`` residual summed
    alongside, so the block sum is accurate to the FF class before the
    ``Mul22``/``Add22`` cross-block combine.  Contract: <= 2^-40 relative
    vs the f64 oracle on long-K rows (doctested in docs/NUMERICS.md).
    ``return_ff=True`` keeps both limbs (FF out) — the f32 hi limb alone
    rounds away the very bits the contract is about.
    """
    B, Sq, H, hd, Skv, KV = _dims(q, k)
    G = H // KV
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    pq, pkv = (-Sq) % bq, (-Skv) % bkv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    nq, nkv = q.shape[1] // bq, k.shape[1] // bkv
    sc = _resolve_scale(scale, hd)

    qb = q.reshape(B, nq, bq, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(B, nkv, bkv, KV, hd).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nkv, bkv, KV, hd).transpose(1, 0, 3, 2, 4)

    q_pos_base = jnp.asarray(q_offset, jnp.int32)
    E = ffmath.CORE

    def one_q_block(iq, qi):
        qi32 = qi.astype(jnp.float32)
        q_pos = q_pos_base + iq * bq + jnp.arange(bq, dtype=jnp.int32)
        shp = (B, KV, G, bq)

        def kv_step(carry, jk):
            m, dh, dl, nh, nl = carry
            den, num = FF(dh, dl), FF(nh, nl)
            kj = kb[jk].astype(jnp.float32)
            vj = vb[jk].astype(jnp.float32)
            # FF scores: TwoProd-exact q*k products, compensated sum over
            # the head dim, Mul212 scale — 2^-44-class logits (an f32
            # dot's ~2^-21 score error would pass straight through exp as
            # relative weight error)
            pshape = (B, KV, G, bq, bkv, hd)
            tph, tpl = T.two_prod(
                jnp.broadcast_to(qi32[..., :, None, :], pshape),
                jnp.broadcast_to(kj[:, :, None, None], pshape))
            s_ff = core_ff.add22_accurate(
                compensated.ff_sum_blocked(tph, axis=-1, block=block),
                compensated.ff_sum_blocked(tpl, axis=-1, block=block))
            s_ff = core_ff.mul212(s_ff, jnp.float32(sc))  # (B,KV,G,bq,bkv)
            kv_pos = jk * bkv + jnp.arange(bkv, dtype=jnp.int32)
            mask = kv_pos[None, :] <= q_pos[:, None] if causal else \
                jnp.ones((bq, bkv), bool)
            mask = jnp.logical_and(mask, (kv_pos < Skv)[None, :])
            full = jnp.broadcast_to(mask[None, None, None], s_ff.hi.shape)
            if kv_len is not None:
                rag = kv_pos[None, :] < kv_len[:, None]
                full = jnp.logical_and(full, rag[:, None, None, None])
            shi = jnp.where(full, s_ff.hi, NEG_INF)
            slo = jnp.where(full, s_ff.lo, jnp.float32(0.0))
            m_new = jnp.maximum(m, shi.max(axis=-1))
            # FF exponentials on the Add212-shifted FF argument
            d_ff = core_ff.add212(FF(shi, slo), -m_new[..., None])
            ph, plo = ffmath.exp22(d_ff.hi, d_ff.lo, E)
            zero = jnp.float32(0.0)
            ph = jnp.where(full, ph, zero)
            plo = jnp.where(full, plo, zero)
            # FF rescale factor alpha = exp(m - m_new), argument exact
            ah, al = T.two_sum(m, -m_new)
            alpha = FF(*ffmath.exp22(ah, al, E))
            # denominator: alpha*den + blocksum(p)  (both limb planes summed)
            bs = core_ff.add22_accurate(
                compensated.ff_sum_blocked(ph, axis=-1, block=block),
                compensated.ff_sum_blocked(plo, axis=-1, block=block))
            den = core_ff.add22(core_ff.mul22(den, alpha), bs)
            # numerator: alpha*num + blocksum(p * v) with TwoProd-exact
            # hi-plane products; the lo-plane products (< 2^-24 relative)
            # ride the residual sum
            vfull = jnp.broadcast_to(vj[:, :, None, None], ph.shape + (hd,))
            th, tl = T.two_prod(jnp.broadcast_to(ph[..., None], vfull.shape),
                                vfull)
            tl = tl + plo[..., None] * vfull
            nb = core_ff.add22_accurate(
                compensated.ff_sum_blocked(th, axis=-2, block=block),
                compensated.ff_sum_blocked(tl, axis=-2, block=block))
            ab = FF(jnp.broadcast_to(alpha.hi[..., None], nb.shape),
                    jnp.broadcast_to(alpha.lo[..., None], nb.shape))
            num = core_ff.add22(core_ff.mul22(num, ab), nb)
            return (m_new, den.hi, den.lo, num.hi, num.lo), None

        m0 = jnp.full(shp, NEG_INF, jnp.float32)
        z1 = jnp.zeros(shp, jnp.float32)
        z2 = jnp.zeros(shp + (hd,), jnp.float32)
        (m, dh, dl, nh, nl), _ = lax.scan(
            kv_step, (m0, z1, z1, z2, z2), jnp.arange(nkv, dtype=jnp.int32))
        den = _ff_safe_den(FF(dh, dl))
        dfull = FF(jnp.broadcast_to(den.hi[..., None], nh.shape),
                   jnp.broadcast_to(den.lo[..., None], nh.shape))
        o = core_ff.div22(FF(nh, nl), dfull)
        return o.hi, o.lo

    ohs, ols = lax.map(lambda args: one_q_block(*args),
                       (jnp.arange(nq, dtype=jnp.int32), qb))

    def assemble(planes):
        out = planes.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * bq, H, hd)
        return out[:, :Sq]

    if return_ff:
        return FF(assemble(ohs), assemble(ols))
    return assemble(ohs).astype(q.dtype)


# ===========================================================================
# pallas tier: the same recurrence as one kernel per (head, q-block) stripe
# ===========================================================================

def _attn_kernel(q_ref, k_ref, v_ref, o_ref, ol_ref,
                 m_sc, dh_sc, dl_sc, nh_sc, nl_sc, qT_sc, pT_sc, plT_sc, *,
                 nkv: int, bq: int, bkv: int, hdp: int,
                 Skv: int, causal: bool, q_offset: int, scale: float):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], NEG_INF)
        dh_sc[...] = jnp.zeros_like(dh_sc[...])
        dl_sc[...] = jnp.zeros_like(dl_sc[...])
        nh_sc[...] = jnp.zeros_like(nh_sc[...])
        nl_sc[...] = jnp.zeros_like(nl_sc[...])

    # FF scores: TwoProd-exact outer products per head-dim slice through a
    # Neumaier cascade (the zero-padded hdp tail contributes exactly 0).
    # The loops index refs by row (``pl.ds`` on the sublane axis): k arrives
    # pre-transposed, q is transposed into scratch, and a row comes back as
    # a column with ``.T`` — Mosaic lowers no value-level dynamic_slice.
    qT_sc[...] = q_ref[0].T                           # (hdp, bq)
    zs = jnp.zeros((bq, bkv), jnp.float32)

    def sbody(d, carry):
        s_, c_, cc_ = carry
        qd = qT_sc[pl.ds(d, 1), :].T                  # (bq, 1)
        kd = k_ref[0, pl.ds(d, 1), :]                 # (1, bkv)
        th, tl = eft.two_prod(jnp.broadcast_to(qd, (bq, bkv)),
                              jnp.broadcast_to(kd, (bq, bkv)))
        s2, e = eft.two_sum(s_, th)
        c2, e2 = eft.two_sum(c_, e)
        return s2, c2, cc_ + e2 + tl

    s_, c_, cc_ = lax.fori_loop(0, hdp, sbody, (zs, zs, zs))
    sh0, e0 = eft.two_sum(s_, c_)
    sh0, sl0 = eft.fast_two_sum(sh0, e0 + cc_)
    sh0, sl0 = eft.mul212(sh0, sl0, jnp.float32(scale))

    row = (jnp.int32(q_offset) + i * bq
           + lax.broadcasted_iota(jnp.int32, (bq, bkv), 0))
    col = j * bkv + lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = col < Skv
    if causal:
        mask = jnp.logical_and(mask, col <= row)
    sh = jnp.where(mask, sh0, jnp.float32(NEG_INF))
    sl = jnp.where(mask, sl0, jnp.float32(0.0))

    m_old = m_sc[:, :1]                               # (bq, 1)
    m_new = jnp.maximum(m_old, jnp.max(sh, axis=1, keepdims=True))
    m_sc[...] = jnp.broadcast_to(m_new, (bq, LANE))
    dh, dl = eft.add212(sh, sl, jnp.broadcast_to(-m_new, sh.shape))
    ph, plo = ffmath.exp22(dh, dl, eft)
    zero = jnp.float32(0.0)
    ph = jnp.where(mask, ph, zero)
    plo = jnp.where(mask, plo, zero)
    ah, al = eft.two_sum(m_old, -m_new)
    alh, all_ = ffmath.exp22(ah, al, eft)             # (bq, 1)

    # denominator: lane-parallel Neumaier cascade over both limb planes
    z = jnp.zeros((bq, LANE), jnp.float32)
    sA, cA, ccA = _lane_cascade(ph, z, z, z, LANE)
    sA, cA, ccA = _lane_cascade(plo, sA, cA, ccA, LANE)
    bs_h, bs_l = _fold_lanes(sA, cA, ccA)             # (bq, 1)
    d0h, d0l = eft.mul22(dh_sc[:, :1], dl_sc[:, :1], alh, all_)
    d1h, d1l = eft.add22(d0h, d0l, bs_h, bs_l)
    dh_sc[...] = jnp.broadcast_to(d1h, (bq, LANE))
    dl_sc[...] = jnp.broadcast_to(d1l, (bq, LANE))

    # numerator block sum: Neumaier cascade over the bkv terms, each an
    # exact TwoProd of the hi plane with the lo-plane product in the
    # compensation stream (p planes transposed into scratch, read by row)
    pT_sc[...] = ph.T                                 # (bkv, bq)
    plT_sc[...] = plo.T
    zn = jnp.zeros((bq, hdp), jnp.float32)

    def body(t, carry):
        s_, c_, cc_ = carry
        pt_h = pT_sc[pl.ds(t, 1), :].T                # (bq, 1)
        pt_l = plT_sc[pl.ds(t, 1), :].T
        vt = v_ref[0, pl.ds(t, 1), :]                 # (1, hdp)
        th, tl = eft.two_prod(jnp.broadcast_to(pt_h, (bq, hdp)),
                              jnp.broadcast_to(vt, (bq, hdp)))
        tl = tl + pt_l * vt
        s2, e = eft.two_sum(s_, th)
        c2, e2 = eft.two_sum(c_, e)
        return s2, c2, cc_ + e2 + tl

    s_, c_, cc_ = lax.fori_loop(0, bkv, body, (zn, zn, zn))
    pvh, e = eft.two_sum(s_, c_)
    pvh, pvl = eft.fast_two_sum(pvh, e + cc_)
    n0h, n0l = eft.mul22(nh_sc[...], nl_sc[...],
                         jnp.broadcast_to(alh, (bq, hdp)),
                         jnp.broadcast_to(all_, (bq, hdp)))
    n1h, n1l = eft.add22(n0h, n0l, pvh, pvl)
    nh_sc[...] = n1h
    nl_sc[...] = n1l

    @pl.when(j == nkv - 1)
    def _flush():
        tiny = jnp.float32(1e-30)
        dh = dh_sc[:, :1]
        ok = dh > tiny
        dh = jnp.where(ok, dh, tiny)
        dl = jnp.where(ok, dl_sc[:, :1], jnp.float32(0.0))
        oh, ol = eft.div22(nh_sc[...], nl_sc[...],
                           jnp.broadcast_to(dh, (bq, hdp)),
                           jnp.broadcast_to(dl, (bq, hdp)))
        o_ref[0] = oh
        ol_ref[0] = ol


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "q_offset", "scale", "interpret",
    "return_ff"))
def flash_attention_pallas(q: Array, k: Array, v: Array, *,
                           causal: bool = True, block_q: int = 32,
                           block_kv: int = 128, q_offset: int = 0,
                           scale: Optional[float] = None,
                           interpret: bool = False,
                           return_ff: bool = False):
    """The compensated online-softmax recurrence as ONE Pallas kernel per
    (batch*head) stripe: grid (B*H, n_q, n_kv), FF numerator/denominator
    accumulators in VMEM scratch carried across the innermost kv steps
    (init at j == 0, Div22-normalize and flush at j == n_kv-1 — the same
    scratch-carry scheme as ``ff_fused``'s trailing reductions).

    GQA is handled by the k/v BlockSpec index maps (head h reads kv head
    ``h // G``) — grouped keys are never materialized per query head.
    Static-length masking only (``kv_len`` ragged batches take the jnp
    tier via dispatch).  Compiled on TPU; interpret-mode elsewhere.
    """
    B, Sq, H, hd, Skv, KV = _dims(q, k)
    G = H // KV
    sc = _resolve_scale(scale, hd)
    bq = _round_up(min(block_q, Sq), SUBLANE)
    bkv = _round_up(min(block_kv, Skv), LANE)
    hdp = _round_up(hd, LANE)

    def prep(x, S, bs):
        # (B, S, Hx, hd) -> (B*Hx, Sp, hdp), f32, padded
        x = jnp.asarray(x, jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, (-S) % bs), (0, 0), (0, hdp - hd)))
        x = x.transpose(0, 2, 1, 3)
        return x.reshape(-1, x.shape[2], hdp)

    q3 = prep(q, Sq, bq)
    k3 = prep(k, Skv, bkv).transpose(0, 2, 1)   # (B*KV, hdp, Skvp)
    v3 = prep(v, Skv, bkv)
    Sqp, Skvp = q3.shape[1], k3.shape[2]
    nq, nkv = Sqp // bq, Skvp // bkv

    def kv_row(h):
        return (h // H) * KV + (h % H) // G

    grid = (B * H, nq, nkv)
    ostruct = jax.ShapeDtypeStruct((B * H, Sqp, hdp), jnp.float32)
    ospec = pl.BlockSpec((1, bq, hdp), lambda h, i, j: (h, i, 0))
    oh3, ol3 = pl.pallas_call(
        functools.partial(_attn_kernel, nkv=nkv, bq=bq, bkv=bkv, hdp=hdp,
                          Skv=Skv, causal=causal, q_offset=int(q_offset),
                          scale=sc),
        out_shape=[ostruct, ostruct],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hdp), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, hdp, bkv), lambda h, i, j: (kv_row(h), 0, j)),
            pl.BlockSpec((1, bkv, hdp), lambda h, i, j: (kv_row(h), j, 0)),
        ],
        out_specs=[ospec, ospec],
        scratch_shapes=[pltpu.VMEM((bq, LANE), jnp.float32),
                        pltpu.VMEM((bq, LANE), jnp.float32),
                        pltpu.VMEM((bq, LANE), jnp.float32),
                        pltpu.VMEM((bq, hdp), jnp.float32),
                        pltpu.VMEM((bq, hdp), jnp.float32),
                        pltpu.VMEM((hdp, bq), jnp.float32),
                        pltpu.VMEM((bkv, bq), jnp.float32),
                        pltpu.VMEM((bkv, bq), jnp.float32)],
        interpret=interpret,
    )(q3, k3, v3)

    def assemble(x):
        x = x.reshape(B, H, Sqp, hdp).transpose(0, 2, 1, 3)
        return x[:, :Sq, :, :hd]

    if return_ff:
        return FF(assemble(oh3), assemble(ol3))
    return assemble(oh3).astype(q.dtype)


# ===========================================================================
# f64 tier: materialized-score oracle (CPU accurate tier / test reference)
# ===========================================================================

@functools.partial(jax.jit, static_argnames=("causal", "q_offset",
                                             "has_kv_len"))
def _attention_f64_jit(q: Array, k: Array, v: Array, kv_len: Array,
                       scale: Array, neg: Array, *, causal: bool,
                       q_offset: int, has_kv_len: bool) -> Array:
    """Native-f64 softmax attention, materialized (Sq, Skv) scores.

    Trace-scoped ``enable_x64`` behind a module-level nested-jit boundary
    (the ``matmul_f64`` idiom — see ``ffmatmul._matmul_f64_jit`` for why
    the boundary is load-bearing); constants inside the scope are traced
    OPERANDS (the scale rides in as an f32 array — a literal would be
    canonicalized to f32 at trace time and poison the f64 multiply)."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    with jax.enable_x64(True):
        c64 = lambda x: lax.convert_element_type(x, jnp.float64)
        q64 = c64(jnp.asarray(q, jnp.float32)).reshape(B, Sq, KV, G, hd)
        k64 = c64(jnp.asarray(k, jnp.float32))
        v64 = c64(jnp.asarray(v, jnp.float32))
        s = jnp.einsum("bqkgd,bskd->bkgqs", q64, k64) * c64(scale)
        q_pos = q_offset + jnp.arange(Sq, dtype=jnp.int32)
        kv_pos = jnp.arange(Skv, dtype=jnp.int32)
        mask = kv_pos[None, :] <= q_pos[:, None] if causal else \
            jnp.ones((Sq, Skv), bool)
        full = jnp.broadcast_to(mask[None, None, None], s.shape)
        if has_kv_len:
            rag = kv_pos[None, :] < kv_len[:, None]
            full = jnp.logical_and(full, rag[:, None, None, None])
        # masked scores get the traced -1e30 operand (f64 exp underflows
        # it to an exact 0 against any real row max) — a -inf LITERAL
        # would be canonicalized to f32 at trace time and poison the tree
        s = jnp.where(full, s, jnp.broadcast_to(c64(neg), s.shape))
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        den = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgqs,bskd->bkgqd", p / den, v64)
        hi = lax.convert_element_type(o, jnp.float32)
        lo = lax.convert_element_type(
            o - lax.convert_element_type(hi, jnp.float64), jnp.float32)

    def assemble(x):
        return x.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)

    return assemble(hi), assemble(lo)


def attention_f64(q: Array, k: Array, v: Array, *, causal: bool = True,
                  q_offset=0, kv_len: Optional[Array] = None,
                  scale: Optional[float] = None, return_ff: bool = False):
    """f64 oracle attention (materializes the (Sq, Skv) score plane —
    validation/scoring shapes only).  ``return_ff=True`` splits the f64
    result into FF limbs (hi = f32 round, lo = f32 residual) so the
    accurate tiers can be compared below the f32 rounding floor."""
    hd = q.shape[-1]
    B = q.shape[0]
    kl = jnp.zeros((B,), jnp.int32) if kv_len is None \
        else jnp.asarray(kv_len, jnp.int32)
    sc = jnp.asarray(_resolve_scale(scale, hd), jnp.float32)
    ng = jnp.asarray(NEG_INF, jnp.float32)
    hi, lo = _attention_f64_jit(q, k, v, kl, sc, ng, causal=causal,
                                q_offset=int(q_offset),
                                has_kv_len=kv_len is not None)
    if return_ff:
        return FF(hi, lo)
    return hi.astype(q.dtype)
