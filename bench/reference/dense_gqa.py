"""Plain reference of the dense GQA decoder that both configurations run.

Straight ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST``: token embedding, then per layer RMSNorm ->
attention (RoPE on q and k, rotate-half convention; grouped-query heads,
head ``h`` reading kv head ``h // (H / KV)``; causal softmax scaled by
``1/sqrt(head_dim)``) -> residual -> RMSNorm -> SwiGLU MLP -> residual,
then the final RMSNorm and the unembedding.  It imports nothing of the
program and runs one whole sequence at a time, layer by layer in a scan.

``mode="fp8"`` is the control: every matrix product's inputs are first
rounded to float8 e4m3 with a scale per row of the activations and per
output column of the weights (the usual W8A8 scaling), and accumulated in
float32.  That is the precision below the bfloat16 compute that the
configurations state.

:func:`score` returns, for each position that predicts a served token,
what the comparison needs, so that no (T, V) logits leave the device.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
E4M3_MAX = 448.0
VOCAB_BLOCKS = 16      # the unembedding is applied in column blocks
BLOCK = {"mlp": "swiglu", "norm": "rmsnorm", "rotary_fraction": 1.0,
         "tie_word_embeddings": False}


def _q8(x, axis):
    """Round ``x`` to float8 e4m3, scaled along ``axis`` to its range."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, mode):
    """(T, k) @ (k, n) in float32 at HIGHEST, or through float8 inputs."""
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HI, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (T, heads, hd); rotate-half RoPE at positions 0..T-1."""
    T, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(h, lp, s, mode):
    T = h.shape[0]
    H, KV, hd = s["H"], s["KV"], s["hd"]
    z = _rms(h, lp["ln1"], s["eps"])
    a = lp["attn"]
    q = _rope(_mm(z, a["wq"], mode).reshape(T, H, hd), s["theta"])
    k = _rope(_mm(z, a["wk"], mode).reshape(T, KV, hd), s["theta"])
    v = _mm(z, a["wv"], mode).reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    sc = jnp.einsum("thd,shd->hts", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(T, H * hd)
    h = h + _mm(o, a["wo"], mode)
    z = _rms(h, lp["ln2"], s["eps"])
    f = lp["ffn"]
    g = jax.nn.silu(_mm(z, f["w_gate"], mode)) * _mm(z, f["w_up"], mode)
    return h + _mm(g, f["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("s_items", "mode"))
def _score(weights, tokens, first, targets, s_items, mode):
    s = dict(s_items)
    h = weights["embed"]["tok"][tokens]
    h, _ = lax.scan(lambda c, lp: (_layer(c, lp, s, mode), None), h,
                    weights["layers"])
    h = _rms(h, weights["final_norm"], s["eps"])
    n = targets.shape[-1]
    hs = lax.dynamic_slice_in_dim(h, first, n, axis=0)
    w = weights["embed"]["unembed"]                          # (d, V)
    V = w.shape[1]
    cuts = [V * i // VOCAB_BLOCKS for i in range(VOCAB_BLOCKS + 1)]
    logits = jnp.concatenate(
        [_mm(hs, w[:, a:b], mode) for a, b in zip(cuts, cuts[1:])],
        axis=-1)                                             # (n, V)
    lse = jax.nn.logsumexp(logits, axis=-1)
    top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    picked = jnp.take_along_axis(logits, targets.T, axis=-1).T  # (k, n)
    return {"max": jnp.max(logits, -1), "lse": lse, "top": top,
            "at": picked}


def score(weights, cfg: Dict, tokens, first: int, targets, mode="f32"):
    """Logit statistics at positions ``first .. first + n - 1``.

    ``tokens``: (T,) int32, the sequence padded to a fixed length (the
    causal mask keeps padding out of every position that is read);
    ``targets``: (k, n) int32 token ids whose logits are wanted at each
    of the n positions.  Returns device arrays ``max`` (n,), ``lse`` (n,),
    ``top`` (n,) (the argmax), and ``at`` (k, n)."""
    runs = {k: cfg["program"].get(k) for k in BLOCK}
    if runs != BLOCK:
        raise ValueError(f"dense_gqa is the block {BLOCK}; the program "
                         f"runs {runs}")
    s = (("H", int(cfg["num_attention_heads"])),
         ("KV", int(cfg["num_key_value_heads"])),
         ("hd", int(cfg["head_dim"])),
         ("eps", float(cfg.get("rms_norm_eps", cfg.get("norm_eps")))),
         ("theta", float(cfg["rope_theta"])))
    return _score(weights, jnp.asarray(tokens, jnp.int32),
                  jnp.int32(first), jnp.asarray(targets, jnp.int32), s, mode)
