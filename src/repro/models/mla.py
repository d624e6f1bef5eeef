"""Multi-head Latent Attention (DeepSeek-V2) with compressed KV cache.

Faithful structure: queries via low-rank (q_lora) path; K/V via a shared
``kv_lora_rank`` latent that IS the cache (plus a decoupled RoPE key slice).
Decode uses the absorbed formulation (q projected into latent space), so
per-token decode touches only (B, S, kv_lora + rope_dim) — the reason MLA
exists.

RoPE follows the config's ``rope_scaling`` (YaRN for DeepSeek-V2) on the
rotary slices, rotate-half on their columns as stored (the published code
de-interleaves ``q_pe``/``k_pe`` first: a fixed permutation of the columns
of ``wq_b`` and ``wkv_a``, which random weights do not see); the softmax
scale is ``1/sqrt(qk_nope + qk_rope)`` times YaRN's ``mscale_all_dim``
squared.

The decode body is split for the paged engine: :func:`mla_project_decode`
(queries and the new token's latent), then the caller writes the latent
into its cache and reads it back, then :func:`mla_attend_latent` (absorbed
scores over the latent, each row masked at its own length, and the output
projection).  :func:`mla_decode` is the same over a contiguous cache.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import repro.ff as ff
from repro.models.config import ModelConfig
from repro.models.layers import (NEG_INF, apply_rope, cast_weight,
                                 dense_init, flash_attention, rms_norm,
                                 rope_for, softmax_mscale)

Array = jnp.ndarray
Params = Dict[str, Any]


def mla_params(key, cfg: ModelConfig) -> Params:
    H = cfg.num_heads
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    return {
        "wq_a": dense_init(ks[0], (cfg.d_model, cfg.q_lora_rank)),
        "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
        "wq_b": dense_init(ks[1], (cfg.q_lora_rank, H * dq)),
        "wkv_a": dense_init(ks[2], (cfg.d_model,
                                    cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
        "wk_b": dense_init(ks[3], (cfg.kv_lora_rank, H * cfg.qk_nope_head_dim)),
        "wv_b": dense_init(ks[4], (cfg.kv_lora_rank, H * cfg.v_head_dim)),
        "wo": dense_init(ks[5], (H * cfg.v_head_dim, cfg.d_model)),
    }


def _rope(x: Array, positions: Array, cfg: ModelConfig) -> Array:
    freqs, scale = rope_for(x.shape[-1], cfg)
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs, scale=scale)


def softmax_scale(cfg: ModelConfig) -> float:
    return softmax_mscale(cfg) / math.sqrt(cfg.qk_nope_head_dim
                                           + cfg.qk_rope_head_dim)


def _project_q(p: Params, x: Array, cfg: ModelConfig, positions: Array):
    B, S, _ = x.shape
    H = cfg.num_heads
    dt = x.dtype
    q_lat = rms_norm(x @ cast_weight(p["wq_a"], dt), p["q_norm"],
                     cfg.norm_eps)
    q = (q_lat @ cast_weight(p["wq_b"], dt)).reshape(
        B, S, H, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = _rope(q[..., cfg.qk_nope_head_dim:], positions, cfg)
    return q_nope, q_rope


def _project_latent(p: Params, x: Array, cfg: ModelConfig, positions: Array):
    """The cached pair of each position: ``c_kv`` (after ``kv_norm``, the
    published ``kv_a_layernorm``) and the rotated ``k_rope``."""
    dt = x.dtype
    kv = x @ cast_weight(p["wkv_a"], dt)
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = _rope(kv[..., cfg.kv_lora_rank:][:, :, None, :],
                   positions, cfg)[:, :, 0]                  # (B,S,rope_dim)
    return c_kv, k_rope


def mla_apply(p: Params, x: Array, cfg: ModelConfig, *,
              positions: Array, attn_impl: str = "fast") -> Array:
    """Training / prefill path: up-project latent to per-head K/V and run
    blockwise attention (memory-feasible: latent is recomputed per block by
    XLA remat rather than cached)."""
    return _attend_full(p, x, cfg, *_project_q(p, x, cfg, positions),
                        *_project_latent(p, x, cfg, positions),
                        attn_impl=attn_impl)


def _attend_full(p: Params, x: Array, cfg: ModelConfig, q_nope, q_rope,
                 c_kv, k_rope, attn_impl: str) -> Array:
    B, S, _ = x.shape
    H = cfg.num_heads
    dt = x.dtype
    k_nope = (c_kv @ cast_weight(p["wk_b"], dt)).reshape(
        B, S, H, cfg.qk_nope_head_dim)
    v = (c_kv @ cast_weight(p["wv_b"], dt)).reshape(B, S, H, cfg.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    m = softmax_mscale(cfg)
    if m != 1.0:            # the flash kernel scales by 1/sqrt(head dim)
        q = q * jnp.asarray(m, q.dtype)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, :, None, :],
                                          (B, S, H, cfg.qk_rope_head_dim))],
                        axis=-1)
    # pad v to qk head dim for the shared flash kernel, then slice back
    dq = q.shape[-1]
    if cfg.v_head_dim < dq:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dq - cfg.v_head_dim)))
    o = flash_attention(q, k, v, causal=True, block_q=cfg.attn_block_q,
                        block_kv=cfg.attn_block_kv, impl=attn_impl)
    o = o[..., :cfg.v_head_dim].reshape(B, S, H * cfg.v_head_dim)
    return o @ cast_weight(p["wo"], dt)


def mla_cache_init(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Params:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def mla_prefill(p: Params, x: Array, cfg: ModelConfig, *, positions: Array,
                cache: Params, attn_impl: str = "fast") -> Tuple[Array, Params]:
    """Prefill: attention over the prompt, and its latents (not per-head
    K/V) written to the cache."""
    q_nope, q_rope = _project_q(p, x, cfg, positions)
    c_kv, k_rope = _project_latent(p, x, cfg, positions)
    cache = dict(cache)
    cache["c_kv"] = lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1)
    cache["k_rope"] = lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), 0, axis=1)
    out = _attend_full(p, x, cfg, q_nope, q_rope, c_kv, k_rope,
                       attn_impl=attn_impl)
    return out, cache


def mla_project_decode(p: Params, x: Array, cfg: ModelConfig,
                       positions: Array):
    """One token per row: ``(q_nope, q_rope, c_new, kr_new)`` for x
    (B, 1, d) at ``positions`` (B, 1); the caller caches ``c_new``
    (B, 1, r) and ``kr_new`` (B, 1, rope_dim)."""
    q_nope, q_rope = _project_q(p, x, cfg, positions)       # (B,1,H,*)
    c_new, kr_new = _project_latent(p, x, cfg, positions)
    return q_nope, q_rope, c_new, kr_new


def mla_attend_latent(p: Params, q_nope: Array, q_rope: Array, c_kv: Array,
                      k_rope: Array, kv_len: Array, cfg: ModelConfig, dt,
                      attn_impl: str = "fast") -> Array:
    """Absorbed decode attention: score = q_nope.Wk_b.c_kv + q_rope.k_rope
    over the latent (B, Smax, r) and rope keys (B, Smax, rope_dim), each
    row masked at its own ``kv_len`` (B,); output = (softmax @ c_kv)
    absorbed through Wv_b, then ``wo``.  Returns (B, 1, d) in ``dt``.

    The latent stays in its stored dtype: each product accumulates in
    float32 (``preferred_element_type``), nothing of the cache is upcast.
    ``attn_impl="fast"`` is a dense masked softmax; any other impl
    re-expresses the absorbed score as one GQA attention call — q = [q_eff
    ‖ q_rope], k = [c_kv ‖ k_rope] with one shared KV head, v = c_kv
    zero-padded to match — through ``ff.attention``'s compensated class."""
    B = q_nope.shape[0]
    H, r = cfg.num_heads, cfg.kv_lora_rank
    f32 = jnp.float32
    wk_b = cast_weight(p["wk_b"], dt).reshape(r, H, cfg.qk_nope_head_dim)
    # absorb: q_eff (B,H,r) = q_nope . wk_b^T
    q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wk_b,
                       preferred_element_type=f32)
    scale = softmax_scale(cfg)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    if attn_impl != "fast":
        dr = cfg.qk_rope_head_dim
        q_cat = jnp.concatenate(
            [q_eff, q_rope[:, 0].astype(f32)], axis=-1)[:, None]
        k_cat = jnp.concatenate([c_kv, k_rope], axis=-1).astype(f32)
        v_lat = jnp.pad(c_kv.astype(f32), ((0, 0), (0, 0), (0, dr)))
        lat = ff.attention(q_cat, k_cat[:, :, None], v_lat[:, :, None],
                           causal=False, kv_len=kv_len, scale=scale,
                           impl=attn_impl)[:, 0, :, :r]
    else:
        cd = c_kv.dtype
        s = (jnp.einsum("bhr,bsr->bhs", q_eff.astype(cd), c_kv,
                        preferred_element_type=f32)
             + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(cd), k_rope,
                          preferred_element_type=f32))
        s = s * scale
        valid = jnp.arange(c_kv.shape[1], dtype=jnp.int32)[None] \
            < kv_len[:, None]
        s = jnp.where(valid[:, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        lat = jnp.einsum("bhs,bsr->bhr", pr.astype(cd), c_kv,
                         preferred_element_type=f32)        # (B,H,r)
    wv_b = cast_weight(p["wv_b"], dt).reshape(r, H, cfg.v_head_dim)
    o = jnp.einsum("bhr,rhd->bhd", lat.astype(dt), wv_b,
                   preferred_element_type=f32)
    o = o.reshape(B, 1, H * cfg.v_head_dim).astype(dt)
    return o @ cast_weight(p["wo"], dt)


def mla_decode(p: Params, x: Array, cfg: ModelConfig, *, pos: Array,
               cache: Params, attn_impl: str = "fast") -> Tuple[Array, Params]:
    """Absorbed decode over a contiguous cache: the new latent written at
    ``pos``, attention over positions ``0 .. pos``."""
    B = x.shape[0]
    assert x.shape[1] == 1
    posv = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope, c_new, kr_new = mla_project_decode(p, x, cfg, posv)
    cache = dict(cache)
    cache["c_kv"] = lax.dynamic_update_slice(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, pos, 0))
    cache["k_rope"] = lax.dynamic_update_slice(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), (0, pos, 0))
    out = mla_attend_latent(p, q_nope, q_rope, cache["c_kv"],
                            cache["k_rope"], pos + 1, cfg, x.dtype,
                            attn_impl=attn_impl)
    return out, cache
