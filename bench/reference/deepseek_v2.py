"""DeepSeek-V2's account in the benchmark, and its plain reference.

The harness resolves a configuration's ``reference`` to this module and
takes from it the shape numbers (``sizes``), the weights from the seed
(``make_weights``), the least work of a decode step for the readers
(``decode_step_flops``, ``decode_step_bytes``) and the reference itself
(``score``).  It imports nothing of the program.

The deployment is expert parallel: the router keeps all of the published
routed experts (``published.n_routed_experts``), and this device holds
``n_routed_experts`` of them from ``deployment.held_offset`` on (one
routing group).  Only the (token, expert) pairs on the held experts are
computed, by the program and here alike; what the absent experts would
add is left out of both, and that partial result goes on to the next
layer.

The reference is straight ``jax.numpy`` in float32, every matrix product
at ``Precision.HIGHEST``, one whole sequence at a time: token embedding,
then per layer RMSNorm -> multi-head latent attention -> residual ->
RMSNorm -> FFN -> residual, then the final RMSNorm and the unembedding.
The attention is the published (non-absorbed) form: queries through the
``q_lora_rank`` bottleneck and its norm; the ``kv_lora_rank`` latent
(after its norm) up-projected to per-head keys and values; the rotary
slices of the queries and of the shared key rotated with YaRN
frequencies (``rope_scaling``); a causal softmax scaled by
``1/sqrt(qk_nope + qk_rope)`` times YaRN's ``mscale_all_dim`` factor
squared.  RoPE is the rotate-half form on the rotary columns as stored:
the published code first de-interleaves ``q_pe`` and ``k_pe``, which is
a fixed permutation of the columns of ``wq_b`` and ``wkv_a``; with random
weights both sides draw the permuted columns, and the program follows the
same convention.  Likewise the published ``kv_b_proj`` is stored here as
its two halves, ``wk_b`` and ``wv_b``.  The first
``first_k_dense_replace`` layers' FFN is the dense SiLU-gated MLP; the
rest route: softmax over every routed expert of float32 logits, the
experts split into ``n_group`` groups each scored by its best expert,
only the ``topk_group`` best groups' experts competing for the top
``num_experts_per_tok``, the gates the raw probabilities times
``routed_scaling_factor`` (``norm_topk_prob`` false); each held expert
is a SiLU-gated MLP of ``moe_intermediate_size``; the
``n_shared_experts`` shared experts (one gated MLP of that many times the
width) apply to every token.

``mode="fp8"`` is the control: every matrix product's inputs are first
rounded to float8 e4m3 with a scale per row of the activations and per
output column of the weights, and accumulated in float32.  That is the
precision below the bfloat16 compute that the configuration states.

:func:`score` returns, for each position that predicts a served token,
what the comparison needs, so that no (T, V) logits leave the device.

The decode counts (:func:`decode_step_flops`, :func:`decode_step_bytes`)
are a shapes-only least count: every weight of the attention, the dense
layer, the shared experts, the routers and the unembedding once, and the
live latents.  They count no routed expert, since routing can send every
token elsewhere; the routed experts' work enters the MoE layer's roofline
from the engine's per-step counter of the pairs on each held expert
(``bench/metrics/moe_roofline.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
E4M3_MAX = 448.0
VOCAB_BLOCKS = 16      # vocabulary matrices are made and applied in blocks
WEIGHT_BYTES = 2       # bfloat16, as the weights are made and served


# -- shapes ---------------------------------------------------------------------

def sizes(conf: Dict) -> Dict[str, int]:
    """The shape numbers of a configuration file, by the names used here."""
    dep = conf.get("deployment", {})
    return {"L": int(conf["num_hidden_layers"]),
            "n_dense": int(conf["first_k_dense_replace"]),
            "d": int(conf["hidden_size"]),
            "H": int(conf["num_attention_heads"]),
            "q_rank": int(conf["q_lora_rank"]),
            "r": int(conf["kv_lora_rank"]),
            "dn": int(conf["qk_nope_head_dim"]),
            "dr": int(conf["qk_rope_head_dim"]),
            "dv": int(conf["v_head_dim"]),
            "F": int(conf["intermediate_size"]),
            "Fe": int(conf["moe_intermediate_size"]),
            "E": int(conf.get("published", {}).get(
                "n_routed_experts", conf["n_routed_experts"])),
            "held": int(conf["n_routed_experts"]),
            "offset": int(dep.get("held_offset", 0)),
            "shared": int(conf["n_shared_experts"]),
            "k": int(conf["num_experts_per_tok"]),
            "groups": int(conf["n_group"]),
            "topk_groups": int(conf["topk_group"]),
            "V": int(conf["vocab_size"])}


def param_counts(s: Dict[str, int]) -> Dict[str, int]:
    """Parameters by part: one MLA layer (norms included), the dense MLP,
    one MoE layer's router, shared experts and held experts, the
    vocabulary matrices, and the total."""
    d, H, r, dr = s["d"], s["H"], s["r"], s["dr"]
    mla = (d * s["q_rank"] + s["q_rank"] * H * (s["dn"] + dr)
           + d * (r + dr) + r * H * s["dn"] + r * H * s["dv"]
           + H * s["dv"] * d)
    norms = s["q_rank"] + r + 2 * d
    dense = 3 * d * s["F"]
    router = d * s["E"]
    shared = 3 * d * s["shared"] * s["Fe"]
    held = s["held"] * 3 * d * s["Fe"]
    n_moe = s["L"] - s["n_dense"]
    layers = (s["L"] * (mla + norms) + s["n_dense"] * dense
              + n_moe * (router + shared + held))
    return {"mla": mla, "dense": dense, "router": router, "shared": shared,
            "held": held, "expert": 3 * d * s["Fe"], "embed": s["V"] * d,
            "unembed": d * s["V"], "total": layers + 2 * s["V"] * d + d}


def _least_matmul(s: Dict[str, int]) -> int:
    """Weights of the matrix products every decoded token goes through
    (no routed expert)."""
    c = param_counts(s)
    n_moe = s["L"] - s["n_dense"]
    return (s["L"] * c["mla"] + s["n_dense"] * c["dense"]
            + n_moe * (c["router"] + c["shared"]) + c["unembed"])


def decode_step_flops(s: Dict[str, int], live_lens: Iterable[int]) -> float:
    """Least model FLOPs of one decode step whose active rows attend over
    ``live_lens`` cached positions each (the new token included): 2 per
    weight of :func:`_least_matmul` per row, and the absorbed attention,
    ``2 * H * (2 r + dr)`` per layer and cached position."""
    lens = list(live_lens)
    attn = 2.0 * s["L"] * s["H"] * (2 * s["r"] + s["dr"]) * sum(lens)
    return 2.0 * _least_matmul(s) * len(lens) + attn


def decode_step_bytes(s: Dict[str, int], live_lens: Iterable[int],
                      kv_bytes: int) -> float:
    """Least bytes one decode step must move: the weights of
    :func:`_least_matmul` once and each row's embedding row, and the live
    latents of every active row (read, and the new entry written)."""
    lens = list(live_lens)
    per_pos = s["L"] * (s["r"] + s["dr"]) * kv_bytes
    weights = WEIGHT_BYTES * (_least_matmul(s) + s["d"] * len(lens))
    return weights + per_pos * (sum(lens) + len(lens))


# -- weights --------------------------------------------------------------------

def seed_key(seed: int) -> np.ndarray:
    """Raw threefry key data for any seed below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} out of range [0, 2**64)")
    return np.asarray([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _normal(key, shape, fan_in):
    return (jax.random.normal(key, shape, jnp.float32)
            * np.float32(1.0 / np.sqrt(fan_in))).astype(jnp.bfloat16)


def _ones(n):
    return jnp.ones((n,), jnp.bfloat16)


def _vocab_matrix(key, rows, cols, fan_in, axis):
    """(rows, cols) made in VOCAB_BLOCKS pieces along ``axis``."""
    shape = [rows, cols]
    blk = -(-shape[axis] // VOCAB_BLOCKS)
    padded = list(shape)
    padded[axis] = blk * VOCAB_BLOCKS
    piece = list(shape)
    piece[axis] = blk
    keys = jax.random.split(key, VOCAB_BLOCKS)

    def body(i, out):
        start = [0, 0]
        start[axis] = i * blk
        return lax.dynamic_update_slice(
            out, _normal(keys[i], tuple(piece), fan_in), tuple(start))

    out = lax.fori_loop(0, VOCAB_BLOCKS, body,
                        jnp.zeros(tuple(padded), jnp.bfloat16))
    return out if padded == shape else lax.slice(out, (0, 0), tuple(shape))


def _mlp(key, d, f):
    ks = jax.random.split(key, 3)
    return {"w_gate": _normal(ks[0], (d, f), d),
            "w_up": _normal(ks[1], (d, f), d),
            "w_down": _normal(ks[2], (f, d), f)}


def _layer(key, s, routed):
    d, H, r, dr = s["d"], s["H"], s["r"], s["dr"]
    ks = jax.random.split(key, 8)
    attn = {"wq_a": _normal(ks[0], (d, s["q_rank"]), d),
            "q_norm": _ones(s["q_rank"]),
            "wq_b": _normal(ks[1], (s["q_rank"], H * (s["dn"] + dr)),
                            s["q_rank"]),
            "wkv_a": _normal(ks[2], (d, r + dr), d),
            "kv_norm": _ones(r),
            "wk_b": _normal(ks[3], (r, H * s["dn"]), r),
            "wv_b": _normal(ks[4], (r, H * s["dv"]), r),
            "wo": _normal(ks[5], (H * s["dv"], d), H * s["dv"])}
    if not routed:
        ffn = _mlp(ks[6], d, s["F"])
    else:
        ke = jax.random.split(ks[6], 4)
        Fe = s["Fe"]
        # one held expert at a time, so the float32 draw stays small
        experts = lax.map(lambda k: _mlp(k, d, Fe),
                          jax.random.split(ke[0], s["held"]))
        ffn = {"router": _normal(ke[1], (d, s["E"]), d),
               "w_gate": experts["w_gate"], "w_up": experts["w_up"],
               "w_down": experts["w_down"],
               "shared": _mlp(ke[2], d, s["shared"] * Fe)}
    return {"ln1": _ones(d), "ln2": _ones(d), "attn": attn, "ffn": ffn}


@functools.lru_cache(maxsize=None)
def _weight_program(items):
    s = dict(items)

    def build(raw):
        key = jax.random.wrap_key_data(raw, impl="threefry2x32")
        k_tok, k_un, k_dense, k_moe = jax.random.split(key, 4)
        n_moe = s["L"] - s["n_dense"]
        out = {"embed": {"tok": _vocab_matrix(k_tok, s["V"], s["d"],
                                              s["V"], 0),
                         "unembed": _vocab_matrix(k_un, s["d"], s["V"],
                                                  s["d"], 1)},
               "layers": lax.map(lambda k: _layer(k, s, True),
                                 jax.random.split(k_moe, n_moe)),
               "final_norm": _ones(s["d"])}
        if s["n_dense"]:
            out["dense_layers"] = lax.map(
                lambda k: _layer(k, s, False),
                jax.random.split(k_dense, s["n_dense"]))
        return out
    return jax.jit(build)


def make_weights(conf: Dict, seed: int):
    """The weights of configuration ``conf`` for ``seed``, on the device,
    in bfloat16 and in the layout the engine reads: ``embed.{tok,
    unembed}``, ``dense_layers`` and ``layers`` (layer stacks, scanned on
    axis 0) of ``ln1``/``ln2``, ``attn.{wq_a, q_norm, wq_b, wkv_a, kv_norm,
    wk_b, wv_b, wo}`` and ``ffn`` (the dense ``w_gate``/``w_up``/``w_down``;
    or ``router`` over every routed expert, the held experts' stacked
    ``w_gate``/``w_up``/``w_down`` and ``shared``), and ``final_norm``.
    Matrices are normal with std ``1/sqrt(fan_in)``, norm weights 1.
    Layers, experts and the vocabulary matrices are made block by block
    inside one program, so its peak stays near the size of the weights."""
    s = sizes(conf)
    return _weight_program(tuple(sorted(s.items())))(
        jnp.asarray(seed_key(seed)))


# -- the reference --------------------------------------------------------------

def _q8(x, axis):
    """Round ``x`` to float8 e4m3, scaled along ``axis`` to its range."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, mode):
    """(T, k) @ (k, n) in float32 at HIGHEST, or through float8 inputs."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HI, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def yarn(conf: Dict):
    """``(inverse frequencies (dr/2,), cos/sin factor, softmax factor)``
    of the configuration's YaRN ``rope_scaling``, by the published
    formulas (``DeepseekV2YarnRotaryEmbedding``, ``yarn_get_mscale``)."""
    dim, base = int(conf["qk_rope_head_dim"]), float(conf["rope_theta"])
    rs = conf["rope_scaling"]
    factor, orig = float(rs["factor"]), int(
        rs["original_max_position_embeddings"])

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / factor
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    cs = mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    return inv.astype(np.float32), cs, mscale(rs["mscale_all_dim"]) ** 2


def _rope(x, inv, cs):
    """x: (T, heads, dr); rotate-half RoPE at positions 0..T-1."""
    T, _, dr = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, None, :] * cs, jnp.sin(ang)[:, None, :] * cs
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(h, a, s, mode):
    T = h.shape[0]
    H, dn, dr, dv = s["H"], s["dn"], s["dr"], s["dv"]
    q = _mm(_rms(_mm(h, a["wq_a"], mode), a["q_norm"], s["eps"]),
            a["wq_b"], mode).reshape(T, H, dn + dr)
    kv = _mm(h, a["wkv_a"], mode)
    c = _rms(kv[:, :s["r"]], a["kv_norm"], s["eps"])
    q_pe = _rope(q[..., dn:], s["inv"], s["cs"])
    k_pe = _rope(kv[:, None, s["r"]:], s["inv"], s["cs"])   # (T, 1, dr)
    k_nope = _mm(c, a["wk_b"], mode).reshape(T, H, dn)
    v = _mm(c, a["wv_b"], mode).reshape(T, H, dv)
    sc = (jnp.einsum("thd,shd->hts", q[..., :dn], k_nope, precision=HI)
          + jnp.einsum("thd,sd->hts", q_pe, k_pe[:, 0], precision=HI))
    sc = sc * (s["sm"] / math.sqrt(dn + dr))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HI).reshape(T, H * dv)
    return _mm(o, a["wo"], mode)


def _gated(z, m, mode):
    g = jax.nn.silu(_mm(z, m["w_gate"], mode)) * _mm(z, m["w_up"], mode)
    return _mm(g, m["w_down"], mode)


def route(z, router, s, mode="f32"):
    """The published gate for z (T, d): ``(gates (T, E), chosen (T, E))``,
    dense over every routed expert: the chosen experts' gates (raw
    softmax probability times the scaling factor) and zero elsewhere."""
    T = z.shape[0]
    E, G = s["E"], s["groups"]
    probs = jax.nn.softmax(_mm(z, router, mode), axis=-1)      # (T, E)
    best = probs.reshape(T, G, E // G).max(-1)
    g_rank = jnp.argsort(jnp.argsort(-best, axis=-1), axis=-1)
    in_group = jnp.repeat(g_rank < s["topk_groups"], E // G, axis=1)
    masked = jnp.where(in_group, probs, 0.0)
    e_rank = jnp.argsort(jnp.argsort(-masked, axis=-1), axis=-1)
    chosen = e_rank < s["k"]
    return jnp.where(chosen, probs * s["scale"], 0.0), chosen


def _moe(z, f, s, mode):
    gates, _ = route(z, f["router"], s, mode)
    mine = lax.dynamic_slice_in_dim(gates, s["offset"], s["held"], axis=1)

    def one(acc, e):
        m = {n: f[n][e] for n in ("w_gate", "w_up", "w_down")}
        g = lax.dynamic_slice_in_dim(mine, e, 1, axis=1)     # (T, 1)
        return acc + g * _gated(z, m, mode), None
    out, _ = lax.scan(one, jnp.zeros_like(z), jnp.arange(s["held"]))
    return out + _gated(z, f["shared"], mode)


def _layer_fwd(h, lp, s, mode, routed):
    h = h + _attention(_rms(h, lp["ln1"], s["eps"]), lp["attn"], s, mode)
    z = _rms(h, lp["ln2"], s["eps"])
    f = _moe(z, lp["ffn"], s, mode) if routed else _gated(z, lp["ffn"],
                                                          mode)
    return h + f


@functools.partial(jax.jit, static_argnames=("s_items", "mode"))
def _score(weights, tokens, first, targets, s_items, mode):
    s = dict(s_items)
    h = weights["embed"]["tok"][tokens].astype(jnp.float32)
    for name, routed in (("dense_layers", False), ("layers", True)):
        if name in weights:
            h, _ = lax.scan(lambda c, lp: (_layer_fwd(c, lp, s, mode,
                                                      routed), None),
                            h, weights[name])
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


def reference_sizes(conf: Dict):
    """The static numbers the reference runs with, as sorted items."""
    if conf.get("norm_topk_prob"):
        raise ValueError("deepseek_v2 is the unnormalised gate "
                         "(norm_topk_prob false)")
    s = sizes(conf)
    inv, cs, sm = yarn(conf)
    s.update(eps=float(conf["rms_norm_eps"]), inv=tuple(float(v)
                                                        for v in inv),
             cs=cs, sm=sm, scale=float(conf["routed_scaling_factor"]))
    return tuple(sorted(s.items()))


def score(weights, cfg: Dict, tokens, first: int, targets, mode="f32"):
    """Logit statistics at positions ``first .. first + n - 1``.

    ``tokens``: (T,) int32, the sequence padded to a fixed length (the
    causal mask keeps padding out of every position that is read);
    ``targets``: (k, n) int32 token ids whose logits are wanted at each
    of the n positions.  Returns device arrays ``max`` (n,), ``lse`` (n,),
    ``top`` (n,) (the argmax), and ``at`` (k, n)."""
    return _score(weights, jnp.asarray(tokens, jnp.int32),
                  jnp.int32(first), jnp.asarray(targets, jnp.int32),
                  reference_sizes(cfg), mode)
