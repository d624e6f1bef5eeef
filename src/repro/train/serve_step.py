"""Serving steps: batched prefill + single-token decode, plus a simple
continuous-batching loop used by the serving example."""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from typing import Optional

from repro.core.policy import PrecisionPolicy
from repro.ff.scope import resolve_policy
from repro.models import prefill, decode_step, init_cache
from repro.models.config import ModelConfig

Array = jnp.ndarray


def make_prefill_step(cfg: ModelConfig,
                      policy: Optional[PrecisionPolicy] = None):
    """policy=None reads the ambient ``repro.ff.policy`` scope at build.
    Jitted, the program is named ``jit_step_prefill``."""
    policy = resolve_policy(policy)

    def step_prefill(params, batch: Dict[str, Array], cache):
        return prefill(params, batch, cfg, cache, policy)
    return step_prefill


def make_decode_step(cfg: ModelConfig,
                     policy: Optional[PrecisionPolicy] = None):
    policy = resolve_policy(policy)

    def step(params, token: Array, pos: Array, cache):
        return decode_step(params, token, pos, cache, cfg, policy)
    return step


def token_logprob(logits: Array, token: Array,
                  policy: Optional[PrecisionPolicy] = None) -> Array:
    """Log-probability of ``token`` under ``logits`` (B, V) -> (B,).

    The normalizer goes through the compensated ``ff.logsumexp`` — at
    serving scale the per-token score is a *loss reduction over the vocab
    axis*, and a naive f32 LSE over a 100k+ vocab loses the very bits the
    confidence consumer cares about.  When the ambient (or explicit)
    policy requests FF transcendentals (``ff_math=True``), the score runs
    the accurate-class ``"ff"`` impl: FF exponentials and an ``ff.math.log``
    of the FF exp-sum, instead of f32-builtin exp/log around the
    compensated sum."""
    import repro.ff as ff

    policy = resolve_policy(policy)
    impl = "ff" if policy.ff_math else None
    lse = ff.logsumexp(jnp.asarray(logits, jnp.float32), axis=-1, impl=impl)
    chosen = jnp.take_along_axis(
        jnp.asarray(logits, jnp.float32), token[:, None], axis=-1)[:, 0]
    return chosen - lse


def token_logprob_ff(logits: Array, token: Array):
    """FF-valued chosen-token log-probability: (B, V), (B,) -> FF of (B,).

    The f32-returning :func:`token_logprob` rounds the score to ~2^-24 at
    the final subtract, which floors any contract tighter than that.  The
    serving accuracy gate (logprob within 2^-40 of the f64 oracle, see
    docs/DESIGN_serving.md) therefore scores through this variant: the
    whole chain — TwoSum max-shift, FF exponentials, compensated exp-sum,
    FF log1p, and the final subtract — stays in FF, and the caller
    compares limb pairs.

    The score is ``(x_tok - m) - log1p(r)`` with ``r`` the exp-sum of every
    entry but one argmax (whose term is exactly 1).  Both parts are <= 0,
    so nothing cancels: a near-certain row (``r`` << 1) keeps its relative
    accuracy, where ``log(1 + r)`` of an FF-rounded ``1 + r`` would lose
    it as 1/r."""
    import repro.core.compensated as compensated
    import repro.core.ff as core_ff
    import repro.core.ffmath as ffmath
    import repro.core.transforms as T
    from repro.core.ff import FF

    x = jnp.asarray(logits, jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    dh, dl = T.two_sum(x, jnp.broadcast_to(-m, x.shape))
    eh, el = ffmath.exp22(dh, dl, ffmath.CORE)
    top = (lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
           == jnp.argmax(x, axis=-1, keepdims=True))
    eh = jnp.where(top, jnp.float32(0), eh)
    el = jnp.where(top, jnp.float32(0), el)
    r = core_ff.add22_accurate(
        compensated.ff_sum_blocked(eh, axis=-1, block=256),
        compensated.ff_sum_blocked(el, axis=-1, block=256))
    l1p = FF(*ffmath.log1p22(r.hi, r.lo, ffmath.CORE))
    chosen = jnp.take_along_axis(x, token[:, None], axis=-1)[:, 0]
    gap = FF(*T.two_sum(chosen, -jnp.squeeze(m, axis=-1)))   # exact
    return core_ff.add22_accurate(gap, FF(-l1p.hi, -l1p.lo))


def greedy_generate(params, cfg: ModelConfig, prompt: Array, max_new: int,
                    cache_len: int,
                    policy: Optional[PrecisionPolicy] = None,
                    extra_inputs: Dict[str, Array] | None = None,
                    return_logprobs: bool = False,
                    eos_id: Optional[int] = None):
    """Greedy decoding loop (jit per step).  prompt: (B, S) int32.

    ``return_logprobs=True`` additionally returns the (B, n) array of
    chosen-token log-probabilities, scored with the compensated FF
    log-sum-exp (:func:`token_logprob`).

    ``eos_id`` (default None = historical behaviour, always ``max_new``
    tokens) enables per-sequence termination: rows that have emitted
    ``eos_id`` keep decoding in lockstep but their subsequent tokens are
    pinned to ``eos_id``, and the loop exits early once EVERY row has
    finished — so ``n <= max_new`` and everything past a row's first EOS
    is EOS.  This is the semantic baseline the continuous-batching engine
    (``repro.serve``) must reproduce token-for-token."""
    B, S = prompt.shape
    cache = init_cache(cfg, B, cache_len)
    batch = {"tokens": prompt}
    if extra_inputs:
        batch.update(extra_inputs)
    pf = jax.jit(make_prefill_step(cfg, policy))
    dc = jax.jit(make_decode_step(cfg, policy))
    pol = resolve_policy(policy)
    score = jax.jit(lambda lg, tk: token_logprob(lg, tk, pol))
    logits, cache = pf(params, batch, cache)
    toks = [jnp.argmax(logits, -1).astype(jnp.int32)]
    lps = [score(logits, toks[-1])] if return_logprobs else None
    done = (toks[-1] == eos_id) if eos_id is not None else None
    pos0 = S + (cfg.num_patches if cfg.family == "vlm" else 0)
    for t in range(max_new - 1):
        if eos_id is not None and bool(done.all()):
            break
        logits, cache = dc(params, toks[-1][:, None], jnp.int32(pos0 + t), cache)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_id), nxt)
            done = done | (nxt == eos_id)
        toks.append(nxt)
        if return_logprobs:
            lps.append(score(logits, toks[-1]))
    out = jnp.stack(toks, axis=1)
    if return_logprobs:
        return out, jnp.stack(lps, axis=1)
    return out
