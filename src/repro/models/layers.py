"""Shared transformer layers: norms, RoPE, flash (blockwise) attention with
GQA + KV cache, SwiGLU MLP, embeddings.

Everything is a pure function over parameter pytrees (dicts of jnp arrays) —
no framework objects — so pjit/shard_map, scan and remat compose freely.

Precision-policy integration (the paper's technique as a feature):
  * ``rms_norm(..., ff_stats=True)`` computes the variance with a compensated
    (TwoSum-cascade) reduction — exact enough that bf16/f32 layernorm drift
    disappears at 500k-token sequence scale.
  * attention softmax accumulators are always f32 (standard), with the
    log-sum-exp renormalization structured like the paper's branch-free ops.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import repro.ff as ff
from repro.models.config import ModelConfig

Array = jnp.ndarray
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis=0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def cast_weight(w: Array, dtype) -> Array:
    """A stored weight in the compute dtype, under ``jax.named_scope("cast")``
    so that a compiled program can report the conversions' device time as
    one part (``repro.obs.parts``).  The scope is HLO metadata only."""
    with jax.named_scope("cast"):
        return w.astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: Array, w: Array, eps: float, ff_stats: bool = False) -> Array:
    """RMSNorm; with ff_stats=True the mean-square is a compensated sum.

    Layout note (§Perf iter 2): the statistics are f32 (and optionally FF),
    but NO f32 (B,S,d) tensor is materialized — only the (B,S,1) scale is
    f32.  With TP-sharded activations, XLA otherwise all-gathers the f32
    pre-convert tensor, doubling the dominant collective (measured on
    llama3-405b train_4k: activation AG/AR were f32, 2x wire bytes).
    """
    xf = x.astype(jnp.float32)
    if ff_stats:
        # one dispatched composite: x*x never round-trips HBM on TPU
        # (fused square+compensated-rowsum kernel; jnp impl elsewhere is
        # bitwise the old ff.sum(xf*xf, block=128)/n formulation)
        ms = ff.mean_sq(xf)[..., None]
    else:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    scale = lax.rsqrt(ms + eps).astype(x.dtype)      # (B,S,1), cheap in bf16
    return x * scale * cast_weight(w, x.dtype)


def layer_norm(x: Array, w: Array, b: Array, eps: float,
               ff_stats: bool = False) -> Array:
    xf = x.astype(jnp.float32)
    if ff_stats:
        # both LayerNorm reductions in one dispatched composite (fused
        # two-pass kernel on TPU reads x from HBM once; the jnp impl is
        # bitwise the old two ff.sum(block=128) passes)
        mu, var = ff.norm_stats(xf)
        mu, var = mu[..., None], var[..., None]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature factor (DeepSeek-V2's
    ``yarn_get_mscale``): ``0.1 * mscale * ln(scale) + 1`` above scale 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(dim: int, cfg: ModelConfig) -> Array:
    """YaRN inverse frequencies (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``):
    interpolated (divided by ``yarn_factor``) below the correction range of
    ``yarn_beta_fast`` .. ``yarn_beta_slow`` rotations over the original
    context, extrapolated (plain RoPE) above it, linearly ramped between."""
    base, factor = cfg.rope_theta, cfg.yarn_factor
    orig = cfg.yarn_original_max_position

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                   # 1: extrapolate, 0: interpolate
    return jnp.asarray((extra / factor) * (1 - keep) + extra * keep,
                       jnp.float32)


def rope_for(dim: int, cfg: ModelConfig) -> Tuple[Array, float]:
    """``(inverse frequencies, cos/sin scale)`` of a rotary slice of
    ``dim`` columns under the config's ``rope_scaling``."""
    if cfg.rope_scaling == "yarn":
        scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
                 / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
        return yarn_freqs(dim, cfg), scale
    if cfg.rope_scaling:
        raise ValueError(f"rope_scaling {cfg.rope_scaling!r}: '' | 'yarn'")
    return rope_freqs(dim, cfg.rope_theta), 1.0


def softmax_mscale(cfg: ModelConfig) -> float:
    """The factor on the softmax scale: YaRN's ``mscale_all_dim`` squared."""
    if cfg.rope_scaling == "yarn" and cfg.yarn_mscale_all_dim:
        return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return 1.0


def apply_rope(x: Array, positions: Array, theta: float,
               freqs: Optional[Array] = None, scale: float = 1.0) -> Array:
    """x: (..., S, H, hd) ; positions: broadcastable to (..., S).
    Rotate-half convention; ``freqs`` (hd/2,) replaces the plain
    frequencies of ``theta`` (YaRN), ``scale`` multiplies cos and sin."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blockwise ("flash") attention — the only memory-feasible form at 32k+
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool,
                    block_q: int, block_kv: int, q_offset=0,
                    impl: str = "fast") -> Array:
    """Online-softmax blockwise attention, via the ``ff.attention`` registry.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd); H = KV * G (GQA).
    Never materializes (Sq, Skv); peak extra memory is
    (B, KV, G, block_q, block_kv).  q_offset: absolute position of q[0]
    (for cached decode/prefill continuation).

    ``impl="fast"`` (the default) is bitwise the historical in-module
    recurrence — the math now lives in ``repro.kernels.ff_attention`` as
    the registry's fast tier.  Passing ``impl="ff"``/``"pallas"``/``"f64"``
    (normally via ``ff.policy(attention=...)`` threaded through the model
    code) swaps in the compensated FF softmax class.
    """
    return ff.attention(q, k, v, causal=causal, q_offset=q_offset,
                        block_q=block_q, block_kv=block_kv, impl=impl)


def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     cache_len: Array, *, impl: str = "fast") -> Array:
    """Single-position attention against a (possibly partially filled) cache.

    q: (B, 1, H, hd); caches: (B, Smax, KV, hd); cache_len: () int32 —
    number of valid cache positions (the new token's K/V must already be
    written at cache_len-1) — or (B,) int32 for ragged serving batches
    where every row has its own filled length.

    The ``impl="fast"`` path below is bitwise the historical dense-softmax
    implementation for scalar ``cache_len``; the per-row form only changes
    the mask broadcast, so each row is bitwise what the scalar call would
    produce for that row's length (masked tails contribute exact zeros) —
    the property the paged serving engine's parity contract rests on.
    Accurate impls route through ``ff.attention(causal=False, kv_len=...)``.
    """
    B, _, H, hd = q.shape
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if impl != "fast":
        kv_len = jnp.broadcast_to(cache_len, (B,))
        return ff.attention(q, k_cache, v_cache, causal=False,
                            kv_len=kv_len, impl=impl)
    _, Smax, KV, _ = k_cache.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    q4 = q.reshape(B, KV, G, hd).astype(jnp.float32) * scale
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", q4, kf)            # (B,KV,G,Smax)
    pos = jnp.arange(Smax, dtype=jnp.int32)
    if cache_len.ndim:
        valid = (pos[None] < cache_len[:, None])[:, None, None]  # (B,1,1,S)
    else:
        valid = (pos < cache_len)[None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (params + apply, train & decode)
# ---------------------------------------------------------------------------

def attn_params(key, cfg: ModelConfig) -> Params:
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (cfg.d_model, cfg.num_heads * hd)),
        "wk": dense_init(k2, (cfg.d_model, cfg.num_kv_heads * hd)),
        "wv": dense_init(k3, (cfg.d_model, cfg.num_kv_heads * hd)),
        "wo": dense_init(k4, (cfg.num_heads * hd, cfg.d_model)),
    }


def attn_apply(p: Params, x: Array, cfg: ModelConfig, *,
               positions: Array, causal: bool = True,
               attn_impl: str = "fast") -> Array:
    """Full-sequence attention (training / prefill)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ cast_weight(p["wq"], dt)).reshape(B, S, cfg.num_heads, hd)
    k = (x @ cast_weight(p["wk"], dt)).reshape(B, S, cfg.num_kv_heads, hd)
    v = (x @ cast_weight(p["wv"], dt)).reshape(B, S, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = flash_attention(q, k, v, causal=causal, block_q=cfg.attn_block_q,
                        block_kv=cfg.attn_block_kv, impl=attn_impl)
    return o.reshape(B, S, cfg.num_heads * hd) @ cast_weight(p["wo"], dt)


def attn_prefill(p: Params, x: Array, cfg: ModelConfig, *, positions: Array,
                 cache: Params, attn_impl: str = "fast") -> Tuple[Array, Params]:
    """Prefill: same as train but also writes the KV cache."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ cast_weight(p["wq"], dt)).reshape(B, S, cfg.num_heads, hd)
    k = (x @ cast_weight(p["wk"], dt)).reshape(B, S, cfg.num_kv_heads, hd)
    v = (x @ cast_weight(p["wv"], dt)).reshape(B, S, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    o = flash_attention(q, k, v, causal=True, block_q=cfg.attn_block_q,
                        block_kv=cfg.attn_block_kv, impl=attn_impl)
    cache = dict(cache)
    cache["k"] = lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
    cache["v"] = lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
    out = o.reshape(B, S, cfg.num_heads * hd) @ cast_weight(p["wo"], dt)
    return out, cache


def attn_decode(p: Params, x: Array, cfg: ModelConfig, *,
                pos: Array, cache: Params,
                attn_impl: str = "fast") -> Tuple[Array, Params]:
    """One-token decode: update cache at ``pos``, attend to cache[:pos+1]."""
    B, S, _ = x.shape
    assert S == 1
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ cast_weight(p["wq"], dt)).reshape(B, 1, cfg.num_heads, hd)
    k = (x @ cast_weight(p["wk"], dt)).reshape(B, 1, cfg.num_kv_heads, hd)
    v = (x @ cast_weight(p["wv"], dt)).reshape(B, 1, cfg.num_kv_heads, hd)
    posv = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    cache = dict(cache)
    cache["k"] = lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
    cache["v"] = lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
    o = decode_attention(q, cache["k"], cache["v"], pos + 1, impl=attn_impl)
    out = o.reshape(B, 1, cfg.num_heads * hd) @ cast_weight(p["wo"], dt)
    return out, cache


def attn_cache_init(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16) -> Params:
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
    }


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_params(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (cfg.d_model, d_ff)),
        "w_up": dense_init(k2, (cfg.d_model, d_ff)),
        "w_down": dense_init(k3, (d_ff, cfg.d_model)),
    }


def mlp_apply(p: Params, x: Array, ff_math: bool = False) -> Array:
    """SwiGLU MLP.  ``ff_math=True`` (policy ``ff_math`` switch) computes
    the silu gate with the FF elementary function (``ff.silu``, ~2^-43)
    instead of the ~2^-24 f32 builtin; the default is bitwise-identical
    to the pre-``ff.math`` library."""
    dt = x.dtype
    pre = x @ cast_weight(p["w_gate"], dt)
    if ff_math:
        g = ff.to_f32(ff.silu(pre.astype(jnp.float32))).astype(dt)
    else:
        g = jax.nn.silu(pre)
    u = x @ cast_weight(p["w_up"], dt)
    return (g * u) @ cast_weight(p["w_down"], dt)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def embed_params(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"tok": dense_init(k1, (cfg.vocab_size, cfg.d_model))}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, (cfg.d_model, cfg.vocab_size))
    return p


def embed_apply(p: Params, tokens: Array, dtype) -> Array:
    return cast_weight(p["tok"], dtype)[tokens]


def unembed_apply(p: Params, x: Array, cfg: ModelConfig,
                  ff_math: bool = False) -> Array:
    """Unembedding (+ optional logit soft-cap).  ``ff_math=True`` runs
    the soft-cap tanh through ``ff.tanh`` — the cap is the LAST op before
    the loss/logprob reductions, so the builtin's ~2^-24 error otherwise
    floors everything the FF loss machinery measures downstream."""
    dt = x.dtype
    w = cast_weight(p["unembed"], dt) if "unembed" in p \
        else cast_weight(p["tok"], dt).T
    logits = x @ w
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        if ff_math:
            t = ff.tanh(logits.astype(jnp.float32) / jnp.float32(c))
            logits = (jnp.float32(c) * ff.to_f32(t)).astype(dt)
        else:
            logits = c * jnp.tanh(logits / c)
    return logits
