"""Model assembly: config -> params / train_forward / prefill / decode.

Families
--------
dense / moe / vlm : decoder-only LM (GQA or MLA attention, dense or MoE FFN)
ssm               : mamba2 SSD stack (attention-free)
hybrid            : jamba-style period structure (1 attn per ``attn_every``
                    layers, MoE FFN every ``moe_every``-th layer)
encdec            : whisper-style encoder-decoder (stub frame embeddings)

All stacks are ``lax.scan`` over layer-stacked params (HLO is O(1) in depth)
with optional ``jax.checkpoint`` (remat) on the body.  Decode threads a
layer-stacked cache through the same scan.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import repro.ff as ff
from repro.core.policy import PrecisionPolicy
from repro.distributed import act_sharding as act_shd
from repro.models import mamba2, mla, moe as moe_lib
from repro.models.config import ModelConfig
from repro.models.layers import (attn_apply, attn_cache_init, attn_decode,
                                 attn_params, attn_prefill, embed_apply,
                                 embed_params, mlp_apply, mlp_params,
                                 rms_norm, unembed_apply)

Array = jnp.ndarray
Params = Dict[str, Any]


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# ===========================================================================
# parameter init
# ===========================================================================

def _layer_params(key, cfg: ModelConfig, layer_idx: int,
                  moe: bool = True) -> Params:
    """One decoder layer (used vmapped over layers for dense stacks)."""
    k1, k2 = jax.random.split(key)
    p: Params = {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
                 "ln2": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.use_mla:
        p["attn"] = mla.mla_params(k1, cfg)
    else:
        p["attn"] = attn_params(k1, cfg)
    if moe and cfg.moe_num_experts and (layer_idx % cfg.moe_every == 0):
        p["ffn"] = moe_lib.moe_params(k2, cfg)
    else:
        p["ffn"] = mlp_params(k2, cfg)
    return p


def _stacked_layers(key, cfg: ModelConfig) -> Params:
    """Stack identical-structure layers along axis 0 for scan: ``layers``,
    and before them ``dense_layers``, the ``first_k_dense_replace``
    leading layers whose FFN is the dense MLP."""
    n_dense = cfg.first_k_dense_replace
    keys = jax.random.split(key, cfg.num_layers)
    if cfg.moe_num_experts and cfg.moe_every != 1:
        raise ValueError("interleaved dense/MoE stacks use the hybrid path")
    init_one = functools.partial(_layer_params, cfg=cfg, layer_idx=0)
    out = {"layers": jax.vmap(init_one)(keys[n_dense:])}
    if n_dense:
        out["dense_layers"] = jax.vmap(functools.partial(
            init_one, moe=False))(keys[:n_dense])
    return out


def layer_stacks(params: Params, cfg: ModelConfig):
    """The decoder's layer stacks in order, each ``(name, first layer)``:
    ``dense_layers`` (the leading dense-FFN layers) when the config has
    them, then ``layers``."""
    n_dense = cfg.first_k_dense_replace if "dense_layers" in params else 0
    return ([("dense_layers", 0)] if n_dense else []) + [("layers", n_dense)]


def init_params(cfg: ModelConfig, key) -> Params:
    ke, kl, kf, kenc = jax.random.split(key, 4)
    params: Params = {"embed": embed_params(ke, cfg),
                      "final_norm": jnp.ones((cfg.d_model,), jnp.float32)}

    if cfg.family in ("dense", "moe", "vlm"):
        params.update(_stacked_layers(kl, cfg))

    elif cfg.family == "ssm":
        keys = jax.random.split(kl, cfg.num_layers)

        def one(k):
            return {"ln": jnp.ones((cfg.d_model,), jnp.float32),
                    "mixer": mamba2.ssd_params(k, cfg)}

        params["layers"] = jax.vmap(one)(keys)

    elif cfg.family == "hybrid":
        period = cfg.attn_every
        assert cfg.num_layers % period == 0
        n_periods = cfg.num_layers // period
        keys = jax.random.split(kl, n_periods)

        def one_period(k):
            ks = jax.random.split(k, period)
            layers = []
            for i in range(period):
                ki1, ki2 = jax.random.split(ks[i])
                lp: Params = {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
                              "ln2": jnp.ones((cfg.d_model,), jnp.float32)}
                if i == cfg.attn_index:
                    lp["mixer_attn"] = attn_params(ki1, cfg)
                else:
                    lp["mixer_ssd"] = mamba2.ssd_params(ki1, cfg)
                if cfg.moe_num_experts and (i % cfg.moe_every == 1):
                    lp["ffn_moe"] = moe_lib.moe_params(ki2, cfg)
                else:
                    lp["ffn_mlp"] = mlp_params(ki2, cfg)
                layers.append(lp)
            return tuple(layers)

        params["layers"] = jax.vmap(one_period)(keys)

    elif cfg.family == "encdec":
        ekeys = jax.random.split(kenc, cfg.encoder_layers)

        def enc_one(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
                    "ln2": jnp.ones((cfg.d_model,), jnp.float32),
                    "attn": attn_params(k1, cfg),
                    "ffn": mlp_params(k2, cfg)}

        params["encoder"] = jax.vmap(enc_one)(ekeys)

        dkeys = jax.random.split(kl, cfg.num_layers)

        def dec_one(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"ln1": jnp.ones((cfg.d_model,), jnp.float32),
                    "ln2": jnp.ones((cfg.d_model,), jnp.float32),
                    "ln3": jnp.ones((cfg.d_model,), jnp.float32),
                    "attn": attn_params(k1, cfg),
                    "xattn": attn_params(k2, cfg),
                    "ffn": mlp_params(k3, cfg)}

        params["layers"] = jax.vmap(dec_one)(dkeys)
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
    else:
        raise ValueError(cfg.family)

    if cfg.family == "vlm":
        params["patch_proj"] = jnp.eye(cfg.d_model, dtype=jnp.float32)

    pdt = jnp.dtype(cfg.param_dtype)
    if pdt != jnp.float32:          # parameters stored in a narrower dtype
        params = jax.tree_util.tree_map(lambda w: w.astype(pdt), params)
    return params


# ===========================================================================
# forward blocks
# ===========================================================================

def _decoder_layer(x: Array, lp: Params, cfg: ModelConfig,
                   policy: PrecisionPolicy, positions: Array) -> Tuple[Array, Array]:
    h = rms_norm(x, lp["ln1"], cfg.norm_eps, ff_stats=policy.ff_reductions)
    if cfg.use_mla:
        a = mla.mla_apply(lp["attn"], h, cfg, positions=positions,
                          attn_impl=policy.attention)
    else:
        a = attn_apply(lp["attn"], h, cfg, positions=positions,
                       attn_impl=policy.attention)
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps, ff_stats=policy.ff_reductions)
    if "router" in lp["ffn"]:
        f, aux = moe_lib.moe_apply(lp["ffn"], h, cfg,
                                   ff_stats=policy.ff_reductions,
                                   ff_math=policy.ff_math)
    else:
        f, aux = (mlp_apply(lp["ffn"], h, ff_math=policy.ff_math),
                  jnp.float32(0))
    return x + f, aux


def _ssm_layer(x: Array, lp: Params, cfg: ModelConfig,
               policy: PrecisionPolicy) -> Array:
    h = rms_norm(x, lp["ln"], cfg.norm_eps, ff_stats=policy.ff_reductions)
    return x + mamba2.ssd_block_apply(lp["mixer"], h, cfg,
                                      ff_math=policy.ff_math)


def _hybrid_period(x: Array, pp, cfg: ModelConfig, policy: PrecisionPolicy,
                   positions: Array) -> Tuple[Array, Array]:
    aux_total = jnp.float32(0)
    for i in range(cfg.attn_every):
        lp = jax.tree_util.tree_map(lambda t: t, pp[i])  # slice view
        h = rms_norm(x, lp["ln1"], cfg.norm_eps, ff_stats=policy.ff_reductions)
        if "mixer_attn" in lp:
            m = attn_apply(lp["mixer_attn"], h, cfg, positions=positions,
                           attn_impl=policy.attention)
        else:
            m = mamba2.ssd_block_apply(lp["mixer_ssd"], h, cfg,
                                       ff_math=policy.ff_math)
        x = x + m
        h = rms_norm(x, lp["ln2"], cfg.norm_eps, ff_stats=policy.ff_reductions)
        if "ffn_moe" in lp:
            f, aux = moe_lib.moe_apply(lp["ffn_moe"], h, cfg,
                                       ff_stats=policy.ff_reductions,
                                       ff_math=policy.ff_math)
            aux_total = aux_total + aux
        else:
            f = mlp_apply(lp["ffn_mlp"], h, ff_math=policy.ff_math)
        x = x + f
    return x, aux_total


def _run_stack(params: Params, x: Array, cfg: ModelConfig,
               policy: PrecisionPolicy, positions: Array) -> Tuple[Array, Array]:
    """Scan the layer stack; returns (hidden, aux_loss)."""
    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, lp):
            h, aux = carry
            h = act_shd.constrain_hidden(h)
            h, a = _decoder_layer(h, lp, cfg, policy, positions)
            return (h, aux + a), None
    elif cfg.family == "ssm":
        def body(carry, lp):
            h, aux = carry
            h = act_shd.constrain_hidden(h)
            return (_ssm_layer(h, lp, cfg, policy), aux), None
    elif cfg.family == "hybrid":
        def body(carry, pp):
            h, aux = carry
            h = act_shd.constrain_hidden(h)
            h, a = _hybrid_period(h, pp, cfg, policy, positions)
            return (h, aux + a), None
    else:
        raise ValueError(cfg.family)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    aux = jnp.float32(0)
    stacks = layer_stacks(params, cfg) if cfg.family in (
        "dense", "moe", "vlm") else [("layers", 0)]
    for name, _ in stacks:
        (x, aux), _ = lax.scan(body, (x, aux), params[name])
    return x, aux


def _encoder_stack(params: Params, frames: Array, cfg: ModelConfig,
                   policy: PrecisionPolicy) -> Array:
    B, S, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body(h, lp):
        z = rms_norm(h, lp["ln1"], cfg.norm_eps, ff_stats=policy.ff_reductions)
        h = h + attn_apply(lp["attn"], z, cfg, positions=positions,
                           causal=False, attn_impl=policy.attention)
        z = rms_norm(h, lp["ln2"], cfg.norm_eps, ff_stats=policy.ff_reductions)
        return h + mlp_apply(lp["ffn"], z,
                             ff_math=policy.ff_math), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = lax.scan(body, frames, params["encoder"])
    return rms_norm(h, params["enc_final_norm"], cfg.norm_eps)


def _encdec_decoder(params: Params, x: Array, enc: Array, cfg: ModelConfig,
                    policy: PrecisionPolicy, positions: Array) -> Array:
    B, Se, _ = enc.shape
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))

    def body(carry, lp):
        h = carry
        z = rms_norm(h, lp["ln1"], cfg.norm_eps, ff_stats=policy.ff_reductions)
        h = h + attn_apply(lp["attn"], z, cfg, positions=positions,
                           attn_impl=policy.attention)
        z = rms_norm(h, lp["ln2"], cfg.norm_eps, ff_stats=policy.ff_reductions)
        h = h + _cross_attn(lp["xattn"], z, enc, cfg, positions, enc_pos,
                            attn_impl=policy.attention)
        z = rms_norm(h, lp["ln3"], cfg.norm_eps, ff_stats=policy.ff_reductions)
        return h + mlp_apply(lp["ffn"], z,
                             ff_math=policy.ff_math), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = lax.scan(body, x, params["layers"])
    return h


def _cross_attn(p: Params, x: Array, enc: Array, cfg: ModelConfig,
                positions: Array, enc_pos: Array,
                attn_impl: str = "fast") -> Array:
    from repro.models.layers import apply_rope, flash_attention
    B, S, _ = x.shape
    Se = enc.shape[1]
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, S, cfg.num_heads, hd)
    k = (enc @ p["wk"].astype(dt)).reshape(B, Se, cfg.num_kv_heads, hd)
    v = (enc @ p["wv"].astype(dt)).reshape(B, Se, cfg.num_kv_heads, hd)
    o = flash_attention(q, k, v, causal=False, block_q=cfg.attn_block_q,
                        block_kv=cfg.attn_block_kv, impl=attn_impl)
    return o.reshape(B, S, cfg.num_heads * hd) @ p["wo"].astype(dt)


# ===========================================================================
# training forward + loss
# ===========================================================================

def chunked_cross_entropy(x: Array, params: Params, targets: Array,
                          cfg: ModelConfig,
                          policy: Optional[PrecisionPolicy] = None) -> Array:
    """Sequence-chunked CE: logits are computed per S-chunk inside a remat'd
    scan and immediately reduced — the (B, S, V) tensor never exists.  At
    vocab 128k+ this is the difference between ~100s of GiB of temp per
    device and ~100s of MiB (measured in the dry-run)."""
    policy = ff.resolve_policy(policy)
    B, S, d = x.shape
    c = cfg.loss_chunk
    if not c or S <= c:
        logits = unembed_apply(params["embed"], x, cfg,
                               ff_math=policy.ff_math)
        return cross_entropy(logits, targets, policy)
    pad = (-S) % c
    mask = jnp.ones((B, S), jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = x.shape[1] // c
    xc = x.reshape(B, nc, c, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, nc, c).transpose(1, 0, 2)
    mc = mask.reshape(B, nc, c).transpose(1, 0, 2)

    def body(carry, args):
        tot, cnt = carry
        xi, ti, mi = args
        xi = act_shd.constrain_hidden(xi)
        logits = unembed_apply(params["embed"], xi, cfg,
                               ff_math=policy.ff_math).astype(jnp.float32)
        if policy.ff_reductions:
            lse = ff.logsumexp(logits, axis=-1)
        else:
            lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, ti[..., None].astype(jnp.int32), axis=-1)[..., 0]
        nll = (lse - gold) * mi
        return (tot + nll.sum(), cnt + mi.sum()), None

    body = jax.checkpoint(body, prevent_cse=False)
    (tot, cnt), _ = lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                             (xc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def cross_entropy(logits: Array, targets: Array,
                  policy: Optional[PrecisionPolicy] = None,
                  mask: Optional[Array] = None) -> Array:
    """Token-mean CE.  With ff_reductions: compensated LSE + loss sum."""
    policy = ff.resolve_policy(policy)
    V = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    if policy.ff_reductions:
        lse = ff.logsumexp(lf, axis=-1)
    else:
        lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None].astype(jnp.int32),
                               axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        mask = (targets >= 0)
    mask = mask.astype(jnp.float32)
    nll = nll * mask
    if policy.ff_reductions:
        tot = ff.sum(nll.reshape(-1), block=1024).to_f32()
        cnt = jnp.maximum(mask.sum(), 1.0)
    else:
        tot = nll.sum()
        cnt = jnp.maximum(mask.sum(), 1.0)
    return tot / cnt


def train_forward(params: Params, batch: Dict[str, Array], cfg: ModelConfig,
                  policy: Optional[PrecisionPolicy] = None
                  ) -> Tuple[Array, Dict]:
    policy = ff.resolve_policy(policy)
    dt = _cdtype(cfg)
    tokens = batch["tokens"]
    targets = batch["targets"]
    B, S = tokens.shape
    x = embed_apply(params["embed"], tokens, dt)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    if cfg.family == "vlm":
        patches = batch["patches"].astype(dt) @ params["patch_proj"].astype(dt)
        x = jnp.concatenate([patches, x], axis=1)
        Pn = patches.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(S + Pn, dtype=jnp.int32), (B, S + Pn))

    if cfg.family == "encdec":
        enc = _encoder_stack(params, batch["frames"].astype(dt), cfg, policy)
        x = _encdec_decoder(params, x, enc, cfg, policy, positions)
        aux = jnp.float32(0)
    else:
        x, aux = _run_stack(params, x, cfg, policy, positions)

    if cfg.family == "vlm":
        x = x[:, -S:]                      # loss over text positions only

    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 ff_stats=policy.ff_reductions)
    loss = chunked_cross_entropy(x, params, targets, cfg, policy)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux}


# ===========================================================================
# serving: prefill + decode
# ===========================================================================

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    """Layer-stacked cache pytree matching the scan structure."""
    def stack(make, n):
        one = make()
        return jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), one)

    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.use_mla:
            return {"layers": stack(
                lambda: mla.mla_cache_init(cfg, batch, max_len, dtype),
                cfg.num_layers)}
        return {"layers": stack(
            lambda: attn_cache_init(cfg, batch, max_len, dtype),
            cfg.num_layers)}
    if cfg.family == "ssm":
        return {"layers": stack(
            lambda: mamba2.ssd_state_init(cfg, batch, jnp.float32),
            cfg.num_layers)}
    if cfg.family == "hybrid":
        period = cfg.attn_every
        n_periods = cfg.num_layers // period
        per = {}
        for i in range(period):
            if i == cfg.attn_index:
                per[f"attn_{i}"] = attn_cache_init(cfg, batch, max_len, dtype)
            else:
                per[f"ssm_{i}"] = mamba2.ssd_state_init(cfg, batch, jnp.float32)
        return {"layers": stack(lambda: per, n_periods)}
    if cfg.family == "encdec":
        dec = stack(lambda: attn_cache_init(cfg, batch, max_len, dtype),
                    cfg.num_layers)
        xkv = stack(lambda: {
            "k": jnp.zeros((batch, cfg.encoder_seq, cfg.num_kv_heads,
                            cfg.resolved_head_dim), dtype),
            "v": jnp.zeros((batch, cfg.encoder_seq, cfg.num_kv_heads,
                            cfg.resolved_head_dim), dtype)},
            cfg.num_layers)
        return {"layers": dec, "cross": xkv}
    raise ValueError(cfg.family)


def _ffn_serve(p: Params, z: Array, cfg: ModelConfig,
               policy: PrecisionPolicy) -> Array:
    """A layer's FFN when serving: the dense MLP, or the dropless MoE
    layer over the held experts (``moe.moe_serve``)."""
    if "router" in p:
        return moe_lib.moe_serve(p, z, cfg, ff_math=policy.ff_math)[0]
    return mlp_apply(p, z, ff_math=policy.ff_math)


def _scan_stacks(body, x: Array, params: Params, cache: Params,
                 cfg: ModelConfig) -> Tuple[Array, Params]:
    """Scan ``body`` over each layer stack with its slice of the cache's
    ``layers`` (one entry per decoder layer, in order)."""
    outs = []
    for name, first in layer_stacks(params, cfg):
        n = jax.tree_util.tree_leaves(params[name])[0].shape[0]
        part = jax.tree_util.tree_map(lambda c: c[first:first + n],
                                      cache["layers"])
        x, new = lax.scan(body, x, (params[name], part))
        outs.append(new)
    cache = dict(cache)
    cache["layers"] = outs[0] if len(outs) == 1 else jax.tree_util.tree_map(
        lambda *cs: jnp.concatenate(cs, axis=0), *outs)
    return x, cache


def prefill(params: Params, batch: Dict[str, Array], cfg: ModelConfig,
            cache: Params, policy: Optional[PrecisionPolicy] = None
            ) -> Tuple[Array, Params]:
    """Run the prompt through the model, filling the cache.  Returns
    (last-position logits, cache)."""
    policy = ff.resolve_policy(policy)
    dt = _cdtype(cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_apply(params["embed"], tokens, dt)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    if cfg.family == "vlm":
        patches = batch["patches"].astype(dt) @ params["patch_proj"].astype(dt)
        x = jnp.concatenate([patches, x], axis=1)
        Pn = patches.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(S + Pn, dtype=jnp.int32), (B, S + Pn))

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, scanned):
            h = carry
            lp, lcache = scanned
            z = rms_norm(h, lp["ln1"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            if cfg.use_mla:
                a, lcache = mla.mla_prefill(lp["attn"], z, cfg,
                                            positions=positions, cache=lcache,
                                            attn_impl=policy.attention)
            else:
                a, lcache = attn_prefill(lp["attn"], z, cfg,
                                         positions=positions, cache=lcache,
                                         attn_impl=policy.attention)
            h = h + a
            z = rms_norm(h, lp["ln2"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            return h + _ffn_serve(lp["ffn"], z, cfg, policy), lcache

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, cache = _scan_stacks(body, x, params, cache, cfg)

    elif cfg.family == "ssm":
        def body(carry, scanned):
            h = carry
            lp, lcache = scanned
            z = rms_norm(h, lp["ln"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            m, new_state = mamba2.ssd_block_apply(
                lp["mixer"], z, cfg, state=None, return_state=True,
                ff_math=policy.ff_math)
            return h + m, new_state

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, new_lcache = lax.scan(body, x, (params["layers"], cache["layers"]))
        cache = {"layers": new_lcache}

    elif cfg.family == "hybrid":
        def body(carry, scanned):
            h = carry
            pp, pcache = scanned
            new_cache = {}
            for i in range(cfg.attn_every):
                lp = pp[i]
                z = rms_norm(h, lp["ln1"], cfg.norm_eps,
                             ff_stats=policy.ff_reductions)
                if "mixer_attn" in lp:
                    a, c = attn_prefill(lp["mixer_attn"], z, cfg,
                                        positions=positions,
                                        cache=pcache[f"attn_{i}"],
                                        attn_impl=policy.attention)
                    new_cache[f"attn_{i}"] = c
                else:
                    a, st = mamba2.ssd_block_apply(
                        lp["mixer_ssd"], z, cfg, return_state=True,
                        ff_math=policy.ff_math)
                    new_cache[f"ssm_{i}"] = st
                h = h + a
                z = rms_norm(h, lp["ln2"], cfg.norm_eps,
                             ff_stats=policy.ff_reductions)
                if "ffn_moe" in lp:
                    f = _ffn_serve(lp["ffn_moe"], z, cfg, policy)
                else:
                    f = mlp_apply(lp["ffn_mlp"], z,
                                  ff_math=policy.ff_math)
                h = h + f
            return h, new_cache

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, new_lcache = lax.scan(body, x, (params["layers"], cache["layers"]))
        cache = {"layers": new_lcache}

    elif cfg.family == "encdec":
        enc = _encoder_stack(params, batch["frames"].astype(dt), cfg, policy)
        B_, Se, _ = enc.shape
        hd = cfg.resolved_head_dim

        def fill_cross(lp, xc):
            k = (enc @ lp["xattn"]["wk"].astype(dt)).reshape(
                B_, Se, cfg.num_kv_heads, hd)
            v = (enc @ lp["xattn"]["wv"].astype(dt)).reshape(
                B_, Se, cfg.num_kv_heads, hd)
            return {"k": k.astype(xc["k"].dtype), "v": v.astype(xc["v"].dtype)}

        cross = jax.vmap(fill_cross)(params["layers"], cache["cross"])

        def body(carry, scanned):
            h = carry
            lp, lcache, xkv = scanned
            z = rms_norm(h, lp["ln1"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            a, lcache = attn_prefill(lp["attn"], z, cfg,
                                     positions=positions, cache=lcache,
                                     attn_impl=policy.attention)
            h = h + a
            z = rms_norm(h, lp["ln2"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            h = h + _cross_attn_cached(lp["xattn"], z, xkv, cfg,
                                       attn_impl=policy.attention)
            z = rms_norm(h, lp["ln3"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            return h + mlp_apply(lp["ffn"], z,
                                 ff_math=policy.ff_math), lcache

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, new_lcache = lax.scan(
            body, x, (params["layers"], cache["layers"], cross))
        cache = {"layers": new_lcache, "cross": cross}
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps,
                 ff_stats=policy.ff_reductions)
    logits = unembed_apply(params["embed"], x, cfg,
                           ff_math=policy.ff_math)
    return logits[:, 0], cache


def _cross_attn_cached(p: Params, x: Array, xkv: Params,
                       cfg: ModelConfig, attn_impl: str = "fast") -> Array:
    from repro.models.layers import flash_attention
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, S, cfg.num_heads, hd)
    o = flash_attention(q, xkv["k"].astype(dt), xkv["v"].astype(dt),
                        causal=False, block_q=cfg.attn_block_q,
                        block_kv=cfg.attn_block_kv, impl=attn_impl)
    return o.reshape(B, S, cfg.num_heads * hd) @ p["wo"].astype(dt)


def decode_step(params: Params, token: Array, pos: Array, cache: Params,
                cfg: ModelConfig, policy: Optional[PrecisionPolicy] = None
                ) -> Tuple[Array, Params]:
    """One decode step.  token: (B, 1) int32; pos: () int32 (write index).
    Returns (logits (B, V), new cache)."""
    policy = ff.resolve_policy(policy)
    dt = _cdtype(cfg)
    x = embed_apply(params["embed"], token, dt)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, scanned):
            h = carry
            lp, lcache = scanned
            z = rms_norm(h, lp["ln1"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            if cfg.use_mla:
                a, lcache = mla.mla_decode(lp["attn"], z, cfg, pos=pos,
                                           cache=lcache,
                                           attn_impl=policy.attention)
            else:
                a, lcache = attn_decode(lp["attn"], z, cfg, pos=pos,
                                        cache=lcache,
                                        attn_impl=policy.attention)
            h = h + a
            z = rms_norm(h, lp["ln2"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            return h + _ffn_serve(lp["ffn"], z, cfg, policy), lcache

        x, cache = _scan_stacks(body, x, params, cache, cfg)

    elif cfg.family == "ssm":
        def body(carry, scanned):
            h = carry
            lp, st = scanned
            z = rms_norm(h, lp["ln"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            m, st = mamba2.ssd_decode_step(lp["mixer"], z, cfg, st,
                                           ff_math=policy.ff_math)
            return h + m, st

        x, new_lcache = lax.scan(body, x, (params["layers"], cache["layers"]))
        cache = {"layers": new_lcache}

    elif cfg.family == "hybrid":
        def body(carry, scanned):
            h = carry
            pp, pcache = scanned
            new_cache = {}
            for i in range(cfg.attn_every):
                lp = pp[i]
                z = rms_norm(h, lp["ln1"], cfg.norm_eps,
                             ff_stats=policy.ff_reductions)
                if "mixer_attn" in lp:
                    a, c = attn_decode(lp["mixer_attn"], z, cfg, pos=pos,
                                       cache=pcache[f"attn_{i}"],
                                       attn_impl=policy.attention)
                    new_cache[f"attn_{i}"] = c
                else:
                    a, st = mamba2.ssd_decode_step(
                        lp["mixer_ssd"], z, cfg, pcache[f"ssm_{i}"],
                        ff_math=policy.ff_math)
                    new_cache[f"ssm_{i}"] = st
                h = h + a
                z = rms_norm(h, lp["ln2"], cfg.norm_eps,
                             ff_stats=policy.ff_reductions)
                if "ffn_moe" in lp:
                    f = _ffn_serve(lp["ffn_moe"], z, cfg, policy)
                else:
                    f = mlp_apply(lp["ffn_mlp"], z,
                                  ff_math=policy.ff_math)
                h = h + f
            return h, new_cache

        x, new_lcache = lax.scan(body, x, (params["layers"], cache["layers"]))
        cache = {"layers": new_lcache}

    elif cfg.family == "encdec":
        def body(carry, scanned):
            h = carry
            lp, lcache, xkv = scanned
            z = rms_norm(h, lp["ln1"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            a, lcache = attn_decode(lp["attn"], z, cfg, pos=pos, cache=lcache,
                                    attn_impl=policy.attention)
            h = h + a
            z = rms_norm(h, lp["ln2"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            h = h + _cross_attn_decode(lp["xattn"], z, xkv, cfg,
                                       attn_impl=policy.attention)
            z = rms_norm(h, lp["ln3"], cfg.norm_eps,
                         ff_stats=policy.ff_reductions)
            return h + mlp_apply(lp["ffn"], z,
                                 ff_math=policy.ff_math), lcache

        x, new_lcache = lax.scan(
            body, x, (params["layers"], cache["layers"], cache["cross"]))
        cache = dict(cache)
        cache["layers"] = new_lcache
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 ff_stats=policy.ff_reductions)
    logits = unembed_apply(params["embed"], x, cfg,
                           ff_math=policy.ff_math)
    return logits[:, 0], cache


def _cross_attn_decode(p: Params, x: Array, xkv: Params,
                       cfg: ModelConfig, attn_impl: str = "fast") -> Array:
    from repro.models.layers import decode_attention
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, 1, cfg.num_heads, hd)
    Se = xkv["k"].shape[1]
    o = decode_attention(q, xkv["k"], xkv["v"], jnp.int32(Se),
                        impl=attn_impl)
    return o.reshape(B, 1, cfg.num_heads * hd) @ p["wo"].astype(dt)
