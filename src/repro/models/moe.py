"""Mixture-of-Experts FFN: a dropless layer for serving, and capacity-based
dispatch for training (GShard-style, but scatter/gather instead of the
O(T*E*C) dispatch einsum so it scales to 160-expert configs).

Serving (:func:`moe_serve`, used by ``models.prefill``, ``models.decode_step``
and the serving engine): the published gate (:func:`moe_gate`: float32
logits, softmax over every routed expert, group-limited greedy top-k,
renormalised or scaled gates), then only the (token, expert) pairs that
land on the experts this device holds (``cfg.moe_held``) count, in one of
two forms.  Where the held experts are few against the top-k, or a block
holds few tokens (a decode step), every held expert runs over every token,
one batched product per projection into which XLA fuses each layer's
slice of the stacked expert weights, summed under gates that are zero
off-route.  Otherwise each sequence's pairs are sorted by expert and run
through ``jax.lax.ragged_dot``, so the rows and buffers stay bounded by the
T x k pairs (on a TPU the kernel computes only the grouped rows, but a
layer's expert weights are first copied out of the stack: PERF.md).
No pair is dropped, so a token's output never depends on its batch
neighbours.  With every expert held it is the whole layer; with a share
it is that device's part of it under expert parallelism (the exchange
between devices is not part of this layer).

Router numerics follow the precision policy: router logits/softmax always in
f32, and with ``ff_reductions`` the load-balance statistics use compensated
sums (router stats are the classic place where f32 accumulation drifts at
million-token batches).

Sharding: expert dim maps to the 'model' mesh axis, token dim to 'data'
(EP x DP).  The scatter/gather lowers to all-to-all under SPMD when token
and expert shardings differ — visible in the dry-run collective table.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import repro.ff as ff
from repro.distributed import act_sharding as act_shd
from repro.models.config import ModelConfig
from repro.models.layers import cast_weight, dense_init, mlp_apply

Array = jnp.ndarray
Params = Dict[str, Any]


def moe_params(key, cfg: ModelConfig) -> Params:
    """Router over every routed expert; expert weights of the held ones."""
    E = cfg.moe_num_experts
    held = cfg.moe_held[1]
    dff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (cfg.d_model, E)),
        "w_gate": dense_init(ks[1], (held, cfg.d_model, dff), in_axis=1),
        "w_up": dense_init(ks[2], (held, cfg.d_model, dff), in_axis=1),
        "w_down": dense_init(ks[3], (held, dff, cfg.d_model), in_axis=1),
    }
    if cfg.moe_shared_experts:
        from repro.models.layers import mlp_params
        p["shared"] = mlp_params(
            ks[4], cfg, d_ff=cfg.moe_shared_experts * dff)
    return p


def moe_apply(p: Params, x: Array, cfg: ModelConfig,
              ff_stats: bool = False,
              ff_math: bool = False) -> Tuple[Array, Array]:
    """x: (B, S, d) -> (out, aux_loss).  ``ff_math`` routes the expert
    (and shared-expert) silu gates through ``ff.silu`` — the same policy
    switch the dense MLP honors; default bitwise-identical."""
    B, S, d = x.shape
    T = B * S
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    if cfg.moe_held != (0, E):
        raise ValueError("the capacity path computes every expert; a held "
                         "share runs through moe_serve")
    dt = x.dtype
    xt = x.reshape(T, d)

    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)   # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                      # (T,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # capacity per expert
    cap = int(max(1, round(k * T * cfg.moe_capacity_factor / E)))

    # position of each (token, slot) within its expert — sort-based instead
    # of a (T*k, E) one-hot cumsum, which is O(T*k*E) memory (4 TB at
    # deepseek train_4k scale); this is O(T*k log T*k) compute, O(T*k) memory
    e_idx = idx.reshape(T * k)
    Tk = T * k
    order = jnp.argsort(e_idx, stable=True)                        # (Tk,)
    sorted_e = e_idx[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=e_idx.dtype))
    pos_sorted = jnp.arange(Tk, dtype=jnp.int32) - starts[sorted_e]
    pos_in_e = jnp.zeros((Tk,), jnp.int32).at[order].set(pos_sorted)
    keep = pos_in_e < cap

    # dispatch: scatter token embeddings into (E, cap, d)
    t_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    buf = jnp.zeros((E, cap, d), dt)
    safe_pos = jnp.where(keep, pos_in_e, cap - 1)
    contrib = jnp.where(keep[:, None], xt[t_idx], 0).astype(dt)
    buf = buf.at[e_idx, safe_pos].add(contrib, mode="drop")

    # expert FFN (batched over E)
    pre = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt))
    if ff_math:
        g = ff.to_f32(ff.silu(pre.astype(jnp.float32))).astype(dt)
    else:
        g = jax.nn.silu(pre)
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    h = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(dt))

    # combine: gather back and weight by gates
    y = h[e_idx, safe_pos]                                         # (T*k,d)
    y = jnp.where(keep[:, None], y, 0) * gate_vals.reshape(T * k, 1).astype(dt)
    out = jnp.zeros((T, d), dt).at[t_idx].add(y)

    if cfg.moe_shared_experts:
        out = out + mlp_apply(p["shared"], xt, ff_math=ff_math)

    # load-balance aux loss (Switch):  E * sum_e f_e * P_e
    if ff_stats:
        me = (ff.sum(probs, axis=0, block=4096).to_f32() / T)
    else:
        me = jnp.mean(probs, axis=0)                               # (E,)
    counts = jnp.zeros((E,), jnp.float32).at[e_idx].add(1.0)
    ce = counts / (T * k)
    aux = E * jnp.sum(me * ce)

    return out.reshape(B, S, d), aux


def moe_gate(router: Array, x: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    """The published gate (DeepSeek-V2's ``MoEGate``) for x (T, d):
    ``(gates (T, k) float32, expert ids (T, k) int32)``.  Logits in float32
    (x and the router upcast, every product at HIGHEST), softmax over all
    ``moe_num_experts``; with ``moe_n_group`` > 1 the experts are split
    into that many equal groups, each scored by its best expert, and only
    the ``moe_topk_group`` best groups' experts compete for the top
    ``moe_top_k`` (others masked to 0); the gates are renormalised to sum
    to 1 (``moe_norm_topk_prob``) or multiplied by ``moe_routed_scaling``."""
    f32 = jnp.float32
    logits = jnp.dot(x.astype(f32), router.astype(f32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    G = cfg.moe_n_group
    if G > 1:
        T, E = scores.shape
        best = scores.reshape(T, G, E // G).max(-1)              # (T, G)
        _, top_g = lax.top_k(best, cfg.moe_topk_group)
        keep = jnp.zeros((T, G), jnp.bool_).at[
            jnp.arange(T)[:, None], top_g].set(True)
        scores = jnp.where(jnp.repeat(keep, E // G, axis=1), scores, 0.0)
    gates, idx = lax.top_k(scores, cfg.moe_top_k)
    if cfg.moe_norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    else:
        gates = gates * f32(cfg.moe_routed_scaling)
    return gates, idx.astype(jnp.int32)


# Every held expert over every token computes held / k times the pairs'
# rows.  It is the form taken up to EVERY_EXPERT_RATIO, or where a block
# holds at most EVERY_EXPERT_ROWS tokens: below the v5e's bf16 balance of
# 197e12 / 819e9 ~ 240 FLOPs a byte the held experts' weights, which the
# pairs read as well once every expert is hit, cost more than the rows.
# Past both, the pairs alone are computed.  On a TPU v5e, DeepSeek-V2's
# 20 held experts for top-6 (3.3x the pairs' rows) ran a 128-512 token
# prefill in 15-31 ms this way and in 33-54 ms as pairs (PERF.md).
EVERY_EXPERT_RATIO = 4
EVERY_EXPERT_ROWS = 256


def moe_serve(p: Params, x: Array, cfg: ModelConfig, ff_math: bool = False,
              valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """Dropless MoE FFN over the held experts.  x: (..., d) -> ``(out,
    pairs)``: ``out`` the held experts' gated sum plus the shared experts,
    ``pairs`` (held,) int32 the (token, expert) pairs routed to each held
    expert.  ``valid`` (x's leading shape, bool) leaves the tokens it
    marks False out of the routed experts (a batch's idle rows); their
    output is then the shared experts' alone.  ``ff_math`` routes the silu
    gates through ``ff.silu``, as in the dense MLP."""
    shape = x.shape
    d = shape[-1]
    # blocks of tokens whose pairs are sorted together: each sequence of a
    # (B, S, d) prompt batch, else all tokens (a decode step's rows)
    nb = shape[0] if x.ndim == 3 and shape[1] > 1 else 1
    xb = act_shd.constrain(x.reshape(nb, -1, d), "dp", None, None)
    Tb, k = xb.shape[1], cfg.moe_top_k
    off, held = cfg.moe_held
    gates, idx = moe_gate(p["router"], xb.reshape(nb * Tb, d), cfg)
    local = (idx - off).reshape(nb, Tb, k)
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine = mine & valid.reshape(nb, Tb, 1)
    gates = jnp.where(mine, gates.reshape(nb, Tb, k), 0.0)
    key = jnp.where(mine, local, held).reshape(nb, Tb * k)
    sizes = jnp.zeros((nb, held + 1), jnp.int32).at[
        jnp.arange(nb)[:, None], key].add(1)[:, :held]
    if held <= EVERY_EXPERT_RATIO * k or Tb <= EVERY_EXPERT_ROWS:
        out = _every_expert(p, xb, local, gates, held, ff_math)
    else:
        out = _routed_pairs(p, xb, key, sizes, gates, ff_math)
    out = out.astype(x.dtype).reshape(-1, d)
    if cfg.moe_shared_experts:
        out = out + mlp_apply(p["shared"], xb.reshape(-1, d),
                              ff_math=ff_math)
    return out.reshape(shape), sizes.sum(0)


def _experts(p: Params, a: Array, proj, ff_math: bool) -> Array:
    """The gated expert MLP, ``proj(a, w)`` one projection."""
    pre = proj(a, p["w_gate"])
    if ff_math:
        g = ff.to_f32(ff.silu(pre.astype(jnp.float32))).astype(pre.dtype)
    else:
        g = jax.nn.silu(pre)
    return proj(g * proj(a, p["w_up"]), p["w_down"])


def _every_expert(p: Params, xb: Array, local: Array, gates: Array,
                  held: int, ff_math: bool) -> Array:
    """Every held expert over all tokens, summed in float32 under the
    (nb, Tb, held) gates, zero where a token did not choose the expert.
    Under a mesh the experts stay on 'model' and the tokens on the data
    axes (the weights are gathered, not the tokens)."""
    dt = xb.dtype

    def proj(a, w):
        spec = "btd,edf->ebtf" if a.ndim == 3 else "ebtd,edf->ebtf"
        return act_shd.constrain(jnp.einsum(spec, a, cast_weight(w, dt)),
                                 "model", "dp", None, None)
    h = _experts(p, xb, proj, ff_math)                    # (held, nb, Tb, d)
    onto = local[..., None] == jnp.arange(held)           # (nb, Tb, k, held)
    g = jnp.where(onto, gates[..., None], 0.0).sum(2)     # (nb, Tb, held)
    return (h.astype(jnp.float32)
            * jnp.moveaxis(g, -1, 0)[..., None]).sum(0)


def _routed_pairs(p: Params, xb: Array, key: Array, sizes: Array,
                  gates: Array, ff_math: bool) -> Array:
    """Each block's Tb x k pairs sorted by held expert (the others last,
    in no group and with gate 0) through one grouped product per
    projection (``jax.lax.ragged_dot``, which on a TPU computes the
    grouped rows alone), scattered back in float32 under their gates.
    Blocks run one after another, so a prompt batch sorts and holds the
    pairs of one sequence at a time."""
    Tb, k = gates.shape[1:]

    def block(args):
        x, key, sizes, gates = args
        order = jnp.argsort(key, stable=True)
        tok = order // k

        def proj(a, w):
            return lax.ragged_dot(a, cast_weight(w, a.dtype), sizes)
        h = _experts(p, x[tok], proj, ff_math)                # (Tb*k, d)
        w = gates.reshape(Tb * k)[order]
        return jnp.zeros(x.shape, jnp.float32).at[tok].add(
            h.astype(jnp.float32) * w[:, None])
    return lax.map(block, (xb, key, sizes, gates))
