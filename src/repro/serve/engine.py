"""Continuous-batching serve engine over the paged FF KV cache.

Scheduling model (the standard production shape, single host):

  * requests enter a bounded FIFO queue; :meth:`ServeEngine.run` drains it;
  * **prefill** runs one request at a time at its EXACT prompt length
    (jit-cached per distinct length — no prompt padding, no wasted
    attention FLOPs) through the stock :func:`repro.models.prefill` via
    ``repro.train.serve_step.make_prefill_step``, then the prompt's K/V
    moves into pages;
  * **decode** advances every running sequence one token per step inside a
    single jitted paged step: per-row positions/lengths, per-row RoPE, a
    paged scatter of the new K/V (inactive rows scatter to the
    out-of-bounds drop page) and a block-table gather feeding the per-row
    ``decode_attention`` — which, for ``impl="fast"``, is bitwise the
    scalar path :func:`~repro.train.serve_step.greedy_generate` uses, so
    the engine is token-for-token the sequential baseline;
  * between decode steps, finished rows (EOS or ``max_new``) are evicted
    (pages back to the free list) and waiting requests join (continuous
    batching) — the batch never drains to refill.

Fault tolerance (see ``docs/DESIGN_robustness.md``): every request ends
with a documented terminal status — ``OK`` / ``TIMEOUT`` / ``REJECTED`` /
``DEGRADED`` / ``FAILED`` — and off-nominal conditions never raise out of
:meth:`step`:

  * admission backpressure: a bounded wait queue (``max_queue``) and
    per-request deadlines (wall-clock ``deadline_s`` or deterministic
    ``deadline_steps``); structurally impossible requests are ``REJECTED``
    at submit, expired ones retire as ``TIMEOUT``;
  * ``reserve="prompt"`` allocates pages lazily (prompt only) instead of
    reserving the whole trajectory; when the pool runs dry mid-decode the
    engine preempts the *youngest* running row (its pages return to the
    free list, the request re-prefills later — greedy decoding is
    deterministic, so the replay is token-for-token identical);
  * with ``ff.guard`` active (or ``guard="check"|"degrade"``), the jitted
    step additionally returns a per-row health flag — non-finite new K/V
    in any layer, a non-finite f32 score, or an FF score violating the
    normalization invariant — and flagged rows are quarantined and
    retried on the fast f32 tier (``DEGRADED``), never silently emitted;
    the paging metadata is audited per flush
    (:meth:`~repro.serve.paged_kv.PagedKVCache.check_integrity`).
  * eos-less decode can batch the device->host sync (``sync_every=N``):
    the four per-row vectors of N steps transfer in one ``device_get``,
    token-for-token identical to N=1 (the next input token stays on
    device).

Crash safety (process lifecycle — the tier around a run): the engine is
**restartable** with exact-replay semantics.

  * :meth:`ServeEngine.snapshot` captures the FULL engine between decode
    steps — paged KV planes (all three kv_modes, FF limb planes
    included), block table + free list, queued and running requests with
    their emitted tokens/scores, completed results, deadlines, and the
    sync/guard counters — as flat numpy arrays + a JSON meta dict;
    :meth:`ServeEngine.restore` rebuilds a fresh engine from them, and
    continuing decodes **token-for-token (FF logprob bit-for-bit)
    identical** to the uninterrupted run (greedy decoding is
    deterministic; the snapshot syncs pending steps first so the resumed
    math starts at a step boundary).  Wall-clock deadlines that expired
    during downtime retire as the documented ``TIMEOUT`` on restore —
    never silently revived — while deterministic ``deadline_steps``
    budgets are unaffected by downtime.
  * a write-ahead request journal (``journal=`` path,
    :class:`repro.serve.journal.RequestJournal`): ``submit()`` appends an
    fsync'd JSONL record BEFORE admission, so accepted requests survive a
    crash and are re-admitted in original order on restore (replaying to
    the same tokens); the log truncates on clean retirement and compacts
    to the unsnapshotted tail whenever a snapshot becomes durable.
  * snapshots persist through the hardened ``repro.checkpoint`` (atomic
    tmp+rename, per-leaf CRC32, schema-versioned manifest, keep-last-3
    fallback ladder); :func:`resume_engine` loads the newest generation
    that VERIFIES — a torn/bit-flipped/stale snapshot falls back warned
    to the previous one, bottoming out at a WAL-only cold replay.
    ``run(snapshot_dir=..., snapshot_every=N)`` snapshots every N decode
    steps through an :class:`~repro.checkpoint.checkpoint.AsyncCheckpointer`
    whose write errors surface into the engine loop via ``poll()`` each
    scheduler iteration (counted in ``guard_stats["snapshot_errors"]``).

Accuracy-critical tier: every emitted token is scored with the FF
token-logprob (:func:`repro.train.serve_step.token_logprob_ff`) — the
full vocab-LSE chain stays in float-float, within 2^-40 of the f64
oracle (gated by ``benchmarks/table_serving.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro import obs as obs_mod
from repro.core.policy import PrecisionPolicy
from repro.ff.guard import FFGuardWarning, health_mask, report_violation
from repro.ff.scope import resolve_policy
from repro.models import init_cache, mla
from repro.models.config import ModelConfig
from repro.models.model import layer_stacks
from repro.models.moe import moe_serve
from repro.models.layers import (apply_rope, cast_weight, decode_attention,
                                 mlp_apply, rms_norm, embed_apply,
                                 unembed_apply)
from repro.train.serve_step import (greedy_generate, make_prefill_step,
                                    token_logprob, token_logprob_ff)
from repro.serve.journal import RequestJournal
from repro.serve.paged_kv import PagedKVCache, ff_merge, ff_split

Array = jnp.ndarray

#: engine snapshot schema version (independent of the checkpoint
#: container's ``FORMAT``); restore() refuses any other version.
SNAPSHOT_SCHEMA = 1

# -- terminal statuses (every submitted request ends in exactly one) --------
OK = "OK"                  # ran to eos/max_new on the requested tier
TIMEOUT = "TIMEOUT"        # deadline expired (queued or mid-decode)
REJECTED = "REJECTED"      # never admitted: bounded queue / impossible size
DEGRADED = "DEGRADED"      # guard quarantined the row; fast-tier retry OK
FAILED = "FAILED"          # no healthy result on any tier
STATUSES = (OK, TIMEOUT, REJECTED, DEGRADED, FAILED)


class UnsupportedModelError(NotImplementedError):
    """A model config outside the engine's supported families, named by
    the offending field (raised at construction, not first request)."""

    def __init__(self, field: str, value: Any, supported: str):
        self.field = field
        self.value = value
        self.supported = supported
        super().__init__(
            f"ServeEngine does not support {field}={value!r}; supported: "
            f"{supported}.  Use repro.train.serve_step.greedy_generate "
            f"(contiguous cache) for this family.")


@dataclasses.dataclass
class Request:
    """One generation request.  ``prompt``: 1-D int32 token ids.

    ``deadline_s`` is a wall-clock budget (seconds from submit);
    ``deadline_steps`` a deterministic scheduler budget (decode steps from
    submit — the testable variant).  Either expiring retires the request
    as ``TIMEOUT`` (with any tokens produced so far)."""
    uid: int
    prompt: np.ndarray
    max_new: int = 16
    deadline_s: Optional[float] = None
    deadline_steps: Optional[int] = None


@dataclasses.dataclass
class GenResult:
    """Completed generation: tokens, f32 scores, FF limb-pair scores, and
    the terminal ``status`` (one of :data:`STATUSES`) with a human
    ``detail`` for every non-``OK`` outcome."""
    uid: int
    tokens: np.ndarray            # (n,) int32, n <= max_new
    logprobs: np.ndarray          # (n,) f32 (compensated-LSE scores)
    logprobs_ff: np.ndarray       # (n, 2) f32 — FF (hi, lo) limb pairs
    prompt_len: int = 0
    status: str = OK
    detail: str = ""


_FAMILIES = ('"dense" (GQA attention, dense FFN) or "moe" (MLA attention, '
             'routed experts in every layer after first_k_dense_replace)')


def _check_cfg(cfg: ModelConfig) -> None:
    if cfg.family == "moe":
        if not cfg.use_mla:
            raise UnsupportedModelError(
                "use_mla", False, 'use_mla=True with family="moe" (the '
                'engine pages GQA K/V only for dense stacks)')
        if not cfg.moe_num_experts:
            raise UnsupportedModelError(
                "moe_num_experts", 0, 'moe_num_experts > 0 with '
                'family="moe"')
        if cfg.moe_every != 1:
            raise UnsupportedModelError(
                "moe_every", cfg.moe_every, "moe_every=1 (interleaved "
                "dense and MoE layers are the hybrid family's)")
        return
    if cfg.family != "dense":
        raise UnsupportedModelError("family", cfg.family, _FAMILIES)
    if cfg.use_mla:
        raise UnsupportedModelError(
            "use_mla", True, 'use_mla=False with family="dense"; MLA is '
            'served with family="moe"')
    if cfg.moe_num_experts:
        raise UnsupportedModelError(
            "moe_num_experts", cfg.moe_num_experts,
            'moe_num_experts=0 with family="dense"; routed experts are '
            'served with family="moe"')


def _bytes_by_dtype(leaves) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for w in leaves:
        out[w.dtype.name] = out.get(w.dtype.name, 0) + int(w.nbytes)
    return out


def _empty_result(req: Request, status: str, detail: str) -> GenResult:
    return GenResult(uid=req.uid, tokens=np.zeros((0,), np.int32),
                     logprobs=np.zeros((0,), np.float32),
                     logprobs_ff=np.zeros((0, 2), np.float32),
                     prompt_len=int(req.prompt.shape[0]),
                     status=status, detail=detail)


#: the engine's guard/robustness event categories (one obs counter each)
GUARD_STAT_KEYS = ("flagged_rows", "quarantined", "preempted",
                   "integrity_rebuilds", "snapshot_errors")


class _GuardStats:
    """``ServeEngine.guard_stats``, backed by obs counters.

    Historically a plain dict; chaos tests and callers read AND mutate it
    (``eng.guard_stats["preempted"] += 1``), and ``snapshot()/restore()``
    round-trip it.  This view keeps that exact mutable-mapping surface
    while storing every count in the engine's
    ``serve_guard_events_total{kind=...}`` counters, so the values show
    up in metrics exports and restored engines RESUME their counts
    (``update`` sets the counters to the persisted values)."""

    def __init__(self, registry: "obs_mod.MetricsRegistry"):
        self._registry = registry
        self._keys = list(GUARD_STAT_KEYS)
        for k in GUARD_STAT_KEYS:
            self._counter(k)

    def _counter(self, key: str) -> "obs_mod.Counter":
        if key not in self._keys:
            self._keys.append(key)
        return self._registry.counter("serve_guard_events_total", kind=key)

    def __getitem__(self, key: str) -> int:
        return self._counter(key).value

    def __setitem__(self, key: str, value: int) -> None:
        self._counter(key).set(int(value))

    def __contains__(self, key: str) -> bool:
        return key in self._keys

    def __iter__(self):
        return iter(tuple(self._keys))

    def __len__(self) -> int:
        return len(self._keys)

    def keys(self):
        return tuple(self._keys)

    def items(self):
        return [(k, self[k]) for k in self._keys]

    def values(self):
        return [self[k] for k in self._keys]

    def get(self, key: str, default=None):
        return self[key] if key in self._keys else default

    def update(self, other) -> None:
        for k, v in dict(other).items():
            self[k] = v

    def __repr__(self) -> str:
        return repr(dict(self.items()))

    def __eq__(self, other) -> bool:
        return dict(self.items()) == other


class ServeEngine:
    """Continuous-batching greedy decoder with a paged KV cache.

    Parameters: ``max_batch`` concurrent rows; ``page_size`` tokens/page;
    ``max_ctx`` per-sequence ceiling (prompt + generated); ``num_pages``
    defaults to a full pool (``max_batch * pages_per_seq``); ``eos_id``
    enables per-sequence termination (None = run to ``max_new``);
    ``kv_mode`` is the page storage format ("bf16" matches the
    ``greedy_generate`` baseline cache bitwise; "ff_bf16" pages FF hi/lo
    limb planes through the shared block table).  The attention impl and
    scoring class follow the ambient ``ff.policy`` (``attention="fast"``
    default; ``ff.policy(attention="ff")`` switches the decode softmax to
    the compensated FF class).

    Robustness knobs: ``max_queue`` bounds the wait queue (overflow =>
    ``REJECTED``, never an exception); ``reserve`` is ``"trajectory"``
    (default: whole-trajectory page reservation at admission — a request
    that joins always completes) or ``"prompt"`` (lazy growth per decode
    step + preempt-and-requeue of the youngest row on pool exhaustion —
    higher occupancy, same tokens); ``guard`` overrides the ambient
    ``ff.guard`` mode for the per-step health probe (None = inherit at
    construction); ``sync_every`` batches the device->host sync for
    eos-less decode (forced to 1 when ``eos_id`` is set — EOS needs the
    token on the host every step).

    Crash-safety knobs: ``journal`` names an fsync'd JSONL write-ahead
    log — ``submit()`` records every request durably before admission,
    and attaching an existing journal replays its unaccounted-for
    requests in original order (see :meth:`attach_journal` /
    :func:`resume_engine`); :meth:`snapshot` / :meth:`restore` freeze and
    rebuild the full engine with token-for-token replay parity.

    Weights: the engine serves them in ``cfg.compute_dtype``.  When
    ``cfg.param_dtype`` differs, every floating leaf of ``params`` is
    converted once, before the first program runs (the first
    :meth:`step` or :meth:`prefill`), and ``self.params`` is that tree
    from then on; no program converts a weight again.  Every weight the
    programs read was rounded to the compute dtype anyway, so every
    matrix product gets the values it got before (on the CPU the served
    tokens and scores are bitwise those of programs that convert in
    place; on a TPU the programs compile differently and agree to the
    compute dtype's rounding, not bit for bit).  The engine never
    mutates or deletes the caller's arrays; a caller that drops its own
    reference after construction lets each stored leaf go as soon as its
    copy is made, so the conversion needs the stored tree plus one leaf.
    """

    def __init__(self, params: Any, cfg: ModelConfig, *,
                 max_batch: int = 8, page_size: int = 16,
                 max_ctx: int = 256, num_pages: Optional[int] = None,
                 eos_id: Optional[int] = None, kv_mode: str = "bf16",
                 policy: Optional[PrecisionPolicy] = None,
                 max_queue: Optional[int] = None,
                 reserve: str = "trajectory",
                 guard: Optional[str] = None,
                 sync_every: int = 1,
                 journal: Optional[str] = None,
                 obs: Optional["obs_mod.Observer"] = None):
        _check_cfg(cfg)
        if reserve not in ("trajectory", "prompt"):
            raise ValueError(f"reserve {reserve!r}: 'trajectory' | 'prompt'")
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        self.params = params
        self._resident = False          # set once _weights() has run
        self.cfg = cfg
        self.policy = resolve_policy(policy)
        self.max_batch = max_batch
        self.eos_id = eos_id
        self.max_queue = max_queue
        self.reserve = reserve
        if guard is None:
            from repro.ff.guard import current_guard
            guard = current_guard().mode
        if guard not in ("off", "check", "degrade"):
            raise ValueError(f"guard {guard!r}: 'off' | 'check' | 'degrade'")
        self.guard_mode = guard
        self.sync_every = 1 if eos_id is not None else int(sync_every)
        pages_per_seq = -(-max_ctx // page_size)
        if num_pages is None:
            num_pages = max_batch * pages_per_seq
        self.kv = PagedKVCache.for_config(
            cfg, num_pages=num_pages, page_size=page_size,
            max_seqs=max_batch, max_ctx=max_ctx, kv_mode=kv_mode)
        self.queue: List[Dict[str, Any]] = []   # {"req", "t_sub", "step_sub"}
        self.results: Dict[int, GenResult] = {}
        # slot -> in-flight request bookkeeping (None = free row)
        self._slots: List[Optional[Dict[str, Any]]] = [None] * max_batch
        self._last_tok = np.zeros((max_batch,), np.int32)
        self._token_dev = jnp.zeros((max_batch,), jnp.int32)
        self._pending: List[Dict[str, Any]] = []  # unsynced decode outputs
        self._admit_seq = 0
        self._auditing = False
        # per-engine observability: a private metrics registry (so tests /
        # concurrent engines never share counts) + the request/step trace
        self.obs = obs if obs is not None else obs_mod.Observer()
        self.guard_stats = _GuardStats(self.obs.registry)
        self._req_trace: Dict[int, Dict[str, Any]] = {}
        self.journal: Optional[RequestJournal] = None
        self._snap_cover: Optional[set] = None  # uids of last async save
        # NOTE: the page planes are deliberately NOT donated — on the CPU
        # backend donation around the layer scan costs a defensive copy
        # per step (measured 2x step latency); the non-donated step keeps
        # the pool update as cheap aliased buffers
        self._decode = jax.jit(self._make_decode_step())
        # no closure holds ``self``: a dropped engine frees its weights at
        # once, not at the cyclic collector's next pass
        policy = self.policy
        self._score = jax.jit(lambda lg, tk: token_logprob(lg, tk, policy))
        def _ff_limbs(lg, tk):
            r = token_logprob_ff(lg, tk)
            return r.hi, r.lo
        self._score_ff = jax.jit(_ff_limbs)
        self._prefill_cache: Dict[int, Any] = {}
        self._decode_built = False
        self.decode_steps = 0
        if journal is not None:
            self.attach_journal(journal)

    # -- jitted paged decode step -----------------------------------------

    def _make_decode_step(self):
        cfg, policy, kv = self.cfg, self.policy, self.kv
        ps, npg = kv.page_size, kv.max_pages
        ff_pages = kv.kv_mode == "ff_bf16"
        probe = self.guard_mode != "off"

        def page_write_read(pl, base, new, at, wpage, woff, gidx):
            """Write each row's new entry ``new`` (B, *tail) of plane
            ``base`` at its page/offset (``at``: the index of the layer
            within ``pl``, () when ``pl`` is one layer's slice), and read
            every row's pages back: (B, npg * ps, *tail), merged to f32
            in FF mode."""
            B = new.shape[0]
            if ff_pages:
                hi, lo = ff_split(new)
                pl[f"{base}_hi"] = pl[f"{base}_hi"].at[
                    at + (wpage, woff)].set(hi, mode="drop")
                pl[f"{base}_lo"] = pl[f"{base}_lo"].at[
                    at + (wpage, woff)].set(lo, mode="drop")
                merged = ff_merge(pl[f"{base}_hi"][at + (gidx,)],
                                  pl[f"{base}_lo"][at + (gidx,)])
            else:
                pdt = pl[base].dtype
                pl[base] = pl[base].at[at + (wpage, woff)].set(
                    new.astype(pdt), mode="drop")
                merged = pl[base][at + (gidx,)]
            return merged.reshape((B, npg * ps) + new.shape[1:])

        def step_decode(params, token, lens, bt, active, planes):
            """token: (B,1) int32; lens: (B,) tokens already cached;
            bt: (B, npg) page table (-1 empty); active: (B,) bool;
            planes: dict of (L, NP, ps, *tail).  Returns (next greedy
            token (B,), its f32 and FF (hi, lo) logprobs, a per-row guard
            flag (constant False with the probe off), updated planes,
            and for a routed-expert model a seventh output, the (L_moe,
            held) int32 pairs routed to each held expert of each MoE
            layer) —
            argmax and BOTH scoring tiers run inside the one jitted step,
            so per decode step the host sees four (B,) vectors (plus the
            flag), not the (B, V) logits.  Math per active row is exactly
            the ``model.decode_step`` body at that row's position.

            Named scopes split the program into the parts of
            ``repro.obs.DECODE_PARTS``: ``cast`` (weights to the compute
            dtype, ``cast_weight``: empty, as the engine serves weights
            already in it), ``attn``, ``kv`` (page write, gather, pool
            update), ``mlp`` (the dense FFN), ``moe`` (gate, sort,
            grouped expert products, combine, shared experts), ``head``
            and ``sample``; the token lookup is ``embed``, which is no
            part."""
            dt = jnp.dtype(cfg.compute_dtype)
            B = token.shape[0]
            NP = next(iter(planes.values())).shape[1]
            with jax.named_scope("embed"):
                x = embed_apply(params["embed"], token, dt)
            with jax.named_scope("kv"):
                # the page/offset every row writes its new K/V to (drop
                # page NP for inactive rows -> scatter is a no-op there)
                rowpage = bt[jnp.arange(B), lens // ps]
                wpage = jnp.where(active, rowpage, jnp.int32(NP))
                woff = lens % ps
                gidx = jnp.maximum(bt, 0)      # gather table (garbage rows
            posv = lens[:, None]               # are masked by lens later)
            rw = functools.partial(page_write_read, wpage=wpage, woff=woff,
                                   gidx=gidx)
            layers = latent_layers if kv.kind == "mla" else gqa_layers
            x, bad, planes, pairs = layers(params, x, planes, posv, lens,
                                           active, rw, dt)
            with jax.named_scope("head"):
                x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                             ff_stats=policy.ff_reductions)
                logits = unembed_apply(params["embed"], x, cfg,
                                       ff_math=policy.ff_math)[:, 0]
            with jax.named_scope("sample"):
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                lp = token_logprob(logits, nxt, policy)
                lp_ff = token_logprob_ff(logits, nxt)
                if probe:
                    # score health: non-finite f32 score, or an FF score
                    # pair that is non-finite / unnormalized
                    # (|lo| > ulp(hi)/2)
                    bad = bad | ~jnp.isfinite(lp) | ~health_mask(lp_ff)
            out = (nxt, lp, lp_ff.hi, lp_ff.lo, bad, planes)
            return out if pairs is None else out + (pairs,)

        def flag_nonfinite(bad, *news):
            # a non-finite new cache entry in any layer poisons the row's
            # cache for every later step: flag at the source
            for new in news:
                bad = bad | ~jnp.isfinite(new.astype(jnp.float32)).all(
                    axis=tuple(range(1, new.ndim)))
            return bad

        def gqa_layers(params, x, planes, posv, lens, active, rw, dt):
            """The dense GQA stack: one scan, each layer's planes sliced
            in and out of it."""
            B = x.shape[0]
            H, KVh = cfg.num_heads, cfg.num_kv_heads
            hd = cfg.resolved_head_dim

            def body(carry, scanned):
                h, bad = carry
                lp = scanned[0]
                pl = dict(zip(sorted(planes), scanned[1:]))
                ap = lp["attn"]
                with jax.named_scope("attn"):
                    z = rms_norm(h, lp["ln1"], cfg.norm_eps,
                                 ff_stats=policy.ff_reductions)
                    q = (z @ cast_weight(ap["wq"], dt)).reshape(B, 1, H, hd)
                    k = (z @ cast_weight(ap["wk"], dt)).reshape(
                        B, 1, KVh, hd)
                    v = (z @ cast_weight(ap["wv"], dt)).reshape(
                        B, 1, KVh, hd)
                    q = apply_rope(q, posv, cfg.rope_theta)
                    k = apply_rope(k, posv, cfg.rope_theta)
                    if probe:
                        bad = flag_nonfinite(bad, k, v)
                with jax.named_scope("kv"):
                    gathered = {base: rw(pl, base, new[:, 0], ())
                                for base, new in (("k", k), ("v", v))}
                with jax.named_scope("attn"):
                    o = decode_attention(q, gathered["k"], gathered["v"],
                                         lens + 1, impl=policy.attention)
                    h = h + (o.reshape(B, 1, H * hd)
                             @ cast_weight(ap["wo"], dt))
                with jax.named_scope("mlp"):
                    z = rms_norm(h, lp["ln2"], cfg.norm_eps,
                                 ff_stats=policy.ff_reductions)
                    f = mlp_apply(lp["ffn"], z, ff_math=policy.ff_math)
                return (h + f, bad), tuple(pl[n] for n in sorted(pl))

            bad0 = jnp.zeros((B,), jnp.bool_)
            (x, bad), updated = lax.scan(
                body, (x, bad0),
                (params["layers"],) + tuple(
                    planes[n] for n in sorted(planes)))
            return x, bad, dict(zip(sorted(planes), updated)), None

        def latent_layers(params, x, planes, posv, lens, active, rw, dt):
            """The MLA stack: the leading dense-FFN layers, then the scan
            of the routed-expert layers; the planes ride in the carry and
            each layer writes and reads its own index of them."""
            B = x.shape[0]

            def layer(h, bad, pl, at, lp):
                with jax.named_scope("attn"):
                    z = rms_norm(h, lp["ln1"], cfg.norm_eps,
                                 ff_stats=policy.ff_reductions)
                    q_nope, q_rope, c_new, kr_new = mla.mla_project_decode(
                        lp["attn"], z, cfg, posv)
                    if probe:
                        bad = flag_nonfinite(bad, c_new, kr_new)
                with jax.named_scope("kv"):
                    c_kv = rw(pl, "c_kv", c_new[:, 0], (at,))
                    k_rope = rw(pl, "k_rope", kr_new[:, 0], (at,))
                with jax.named_scope("attn"):
                    h = h + mla.mla_attend_latent(
                        lp["attn"], q_nope, q_rope, c_kv, k_rope, lens + 1,
                        cfg, dt, attn_impl=policy.attention)
                routed = "router" in lp["ffn"]
                with jax.named_scope("moe" if routed else "mlp"):
                    z = rms_norm(h, lp["ln2"], cfg.norm_eps,
                                 ff_stats=policy.ff_reductions)
                    if routed:
                        f, pairs = moe_serve(lp["ffn"], z, cfg,
                                             ff_math=policy.ff_math,
                                             valid=active)
                    else:
                        f = mlp_apply(lp["ffn"], z, ff_math=policy.ff_math)
                        pairs = None
                return h + f, bad, pl, pairs

            bad = jnp.zeros((B,), jnp.bool_)
            pl = dict(planes)
            for name, first in layer_stacks(params, cfg):
                if name == "dense_layers":
                    for i in range(cfg.first_k_dense_replace):
                        lp = jax.tree_util.tree_map(lambda w: w[i],
                                                    params[name])
                        x, bad, pl, _ = layer(x, bad, pl, i, lp)
                    continue

                def body(carry, scanned):
                    h, bad, pl = carry
                    lp, at = scanned
                    h, bad, pl, pairs = layer(h, bad, pl, at, lp)
                    return (h, bad, pl), pairs
                n = jax.tree_util.tree_leaves(params[name])[0].shape[0]
                (x, bad, pl), pairs = lax.scan(
                    body, (x, bad, pl),
                    (params[name], first + jnp.arange(n, dtype=jnp.int32)))
            return x, bad, pl, pairs

        return step_decode

    # -- request lifecycle -------------------------------------------------

    def _trace_submit(self, uid: int) -> None:
        """Open the request's span timeline (idempotent per uid — preempt
        re-submission keeps the original submit timestamp)."""
        if uid not in self._req_trace:
            self._req_trace[uid] = {"submit": self.obs.trace.now(),
                                    "admit": None}
            self.obs.trace.name_request_track(uid)

    def _set_result(self, res: GenResult) -> None:
        """The single terminal-result sink: records the result AND, with
        a journal attached, durably marks the uid retired (truncating the
        log once every journaled request has a terminal status).  Closes
        the request's trace spans: a ``decode`` child (admission ->
        retire) when the request ran, and the top-level ``request`` span
        (submit -> retire) carrying the terminal status."""
        self.results[res.uid] = res
        tr = self._req_trace.pop(res.uid, None)
        if tr is not None:
            now = self.obs.trace.now()
            tid = self.obs.trace.request_tid(res.uid)
            if tr["admit"] is not None:
                self.obs.trace.complete("decode", tr["admit"],
                                        now - tr["admit"], tid=tid)
            self.obs.trace.complete(
                "request", tr["submit"], now - tr["submit"], tid=tid,
                args={"status": res.status, "uid": int(res.uid),
                      "tokens": int(res.tokens.shape[0]),
                      "detail": res.detail})
            self.obs.registry.counter("serve_requests_total",
                                      status=res.status).inc()
            self.obs.registry.counter("serve_tokens_emitted_total").inc(
                int(res.tokens.shape[0]))
        if self.journal is not None:
            self.journal.retire(res.uid, res.status)

    def submit(self, req: Request) -> str:
        """Enqueue a request.  Returns ``"QUEUED"`` or, when the request
        can never be served (bounded queue full, prompt + max_new over
        ``max_ctx``, or a trajectory larger than the whole pool), records
        a ``REJECTED`` result and returns it — submission never raises.
        With a journal attached the request is journaled (fsync'd)
        BEFORE admission: an accepted request survives a crash."""
        if self.journal is not None:
            self.journal.append(req, step_sub=self.decode_steps)
        return self._submit(req, t_sub=time.monotonic(),
                            step_sub=self.decode_steps, bounded=True)

    def _submit(self, req: Request, *, t_sub: float, step_sub: int,
                bounded: bool) -> str:
        """Admission checks + enqueue.  ``bounded=False`` (journal
        replay) skips the queue bound — the request was already accepted
        once; structural impossibility still rejects."""
        self._trace_submit(req.uid)
        S = int(req.prompt.shape[0])
        total = S + req.max_new
        max_ctx = self.kv.max_pages * self.kv.page_size
        if total > max_ctx:
            self._set_result(_empty_result(
                req, REJECTED, f"prompt+max_new = {total} exceeds "
                f"max_ctx = {max_ctx}"))
            return REJECTED
        if self.kv.pages_for(total) > self.kv.num_pages:
            self._set_result(_empty_result(
                req, REJECTED, f"trajectory needs "
                f"{self.kv.pages_for(total)} pages; pool has "
                f"{self.kv.num_pages}"))
            return REJECTED
        if bounded and self.max_queue is not None \
                and len(self.queue) >= self.max_queue:
            self._set_result(_empty_result(
                req, REJECTED, f"wait queue full (max_queue = "
                f"{self.max_queue})"))
            return REJECTED
        self.queue.append({"req": req, "t_sub": t_sub,
                           "step_sub": step_sub})
        return "QUEUED"

    def status(self, uid: int) -> str:
        """Lifecycle status for a submitted uid: a terminal status from
        :data:`STATUSES`, else ``"RUNNING"`` / ``"QUEUED"``."""
        if uid in self.results:
            return self.results[uid].status
        for s in self._slots:
            if s is not None and s["req"].uid == uid:
                return "RUNNING"
        if any(q["req"].uid == uid for q in self.queue):
            return "QUEUED"
        raise KeyError(f"unknown request uid {uid}")

    def _weights(self) -> Any:
        """The params every program reads: on the first call, the caller's
        tree with every floating leaf converted to the compute dtype when
        ``cfg.param_dtype`` differs from it.  Leaf by leaf: the engine's
        reference to a stored leaf goes once its copy is ready, so a
        caller that kept no reference of its own frees each one as the
        conversion proceeds.  Records a ``weights_resident`` instant and
        the ``serve_weight_bytes{dtype}`` gauges."""
        if self._resident:
            return self.params
        self._resident = True
        t0 = time.perf_counter()
        dt = jnp.dtype(self.cfg.compute_dtype)
        leaves, tree = jax.tree_util.tree_flatten(self.params)
        self.params = None
        before = sum(int(w.nbytes) for w in leaves)
        converted = 0
        if jnp.dtype(self.cfg.param_dtype) != dt:
            for i in range(len(leaves)):
                if jnp.issubdtype(leaves[i].dtype, jnp.floating) \
                        and leaves[i].dtype != dt:
                    leaves[i] = jax.block_until_ready(
                        jnp.asarray(leaves[i], dt))
                    converted += 1
        self.params = jax.tree_util.tree_unflatten(tree, leaves)
        after = _bytes_by_dtype(leaves)
        for name, n in after.items():
            self.obs.registry.gauge("serve_weight_bytes", dtype=name).set(n)
        self.obs.trace.instant("weights_resident", args={
            "leaves": converted, "dtype": dt.name,
            "bytes_before": before,
            "bytes_after": sum(after.values()),
            "seconds": time.perf_counter() - t0})
        return self.params

    def _prefill_fn(self, S: int):
        """Exact-length prefill, jit-cached per distinct prompt length."""
        if S not in self._prefill_cache:
            step = make_prefill_step(self.cfg, self.policy)
            self._prefill_cache[S] = jax.jit(step)
            self._program_built("jit_step_prefill", prompt_len=S)
        return self._prefill_cache[S]

    def _program_built(self, name: str, prompt_len: Optional[int] = None,
                       compiled_text=None) -> None:
        """Count a program the engine builds (``serve_programs_built_total``
        and a ``program_built`` instant: a build inside a measured window
        is a compile there).  Under ``obs.enable()``, ``compiled_text``
        (a thunk giving the optimized HLO) publishes the program's named
        parts as a ``program`` metadata record."""
        self.obs.registry.counter("serve_programs_built_total",
                                  program=name).inc()
        self.obs.trace.instant("program_built", args={
            "name": name, "prompt_len": prompt_len})
        if compiled_text is not None and obs_mod.enabled():
            module, parts = obs_mod.program_parts(compiled_text())
            self.obs.trace.metadata("program", {"name": module,
                                                "parts": parts})

    def prefill(self, prompt: np.ndarray) -> Tuple[Array, Any]:
        """Exact-length prefill of one 1-D prompt: ``(logits (1, V),
        cache)``.  Admission runs it; an accuracy check can re-run it to
        recover the logits a request's first token was scored on."""
        # the prefill cache dtype IS the page fidelity: bf16 matches the
        # greedy_generate baseline cache bitwise; the f32 / FF page modes
        # keep the full compute-precision K/V
        cache_dt = jnp.bfloat16 if self.kv.kv_mode == "bf16" \
            else jnp.float32
        S = int(prompt.shape[0])
        cache = init_cache(self.cfg, 1, S, dtype=cache_dt)
        return self._prefill_fn(S)(
            self._weights(), {"tokens": jnp.asarray(prompt[None])}, cache)

    def _deadline_passed(self, req: Request, t_sub: float,
                         step_sub: int) -> bool:
        if req.deadline_s is not None and \
                time.monotonic() - t_sub > req.deadline_s:
            return True
        if req.deadline_steps is not None and \
                self.decode_steps - step_sub >= req.deadline_steps:
            return True
        return False

    def _expire_queue(self) -> None:
        kept = []
        for q in self.queue:
            if self._deadline_passed(q["req"], q["t_sub"], q["step_sub"]):
                self._set_result(_empty_result(
                    q["req"], TIMEOUT, "deadline expired while queued"))
            else:
                kept.append(q)
        self.queue = kept

    def _admit(self) -> None:
        """Join waiting requests into free rows while pages allow (FIFO —
        no request starves behind an unschedulable head-of-line)."""
        if not self.queue:
            return
        with obs_mod.span(self.obs, "serve.admit"):
            self._admit_queue()

    def _admit_queue(self) -> None:
        admitted = False
        while self.queue:
            q = self.queue[0]
            req = q["req"]
            S = int(req.prompt.shape[0])
            total = S + req.max_new
            slot = next((i for i, s in enumerate(self._slots) if s is None),
                        None)
            need = total if self.reserve == "trajectory" else S
            if slot is None or not self.kv.can_alloc(need):
                break
            self.queue.pop(0)
            tr = self._req_trace.get(req.uid)
            ts_adm = self.obs.trace.now()
            tid = self.obs.trace.request_tid(req.uid)
            if tr is not None:
                self.obs.trace.complete("queued", tr["submit"],
                                        ts_adm - tr["submit"], tid=tid)
            if self.reserve == "trajectory":
                self.kv.alloc(slot, total)  # reserve the whole trajectory
                self.kv.seq_lens[slot] = S  # ...but only S tokens are live
            else:
                self.kv.alloc(slot, S)      # lazy: grow() per decode step
            with obs_mod.span(self.obs, "serve.prefill", uid=int(req.uid),
                              prompt_len=S):
                tok, lp, lp_ff = self._prefill_first(slot, req.prompt)
            # the request's prefill ends with its first token and both
            # scores on the host
            ts_pf = self.obs.trace.now()
            self.obs.trace.complete("prefill", ts_adm, ts_pf - ts_adm,
                                    tid=tid, args={"prompt_len": S})
            self.obs.registry.histogram(
                "serve_prefill_seconds").observe((ts_pf - ts_adm) / 1e6)
            if tr is not None:
                tr["admit"] = ts_pf
            state = {"req": req, "prompt_len": S,
                     "tokens": [tok], "logprobs": [lp],
                     "logprobs_ff": [lp_ff],
                     "pending": 0, "start_step": self.decode_steps,
                     "t_sub": q["t_sub"], "step_sub": q["step_sub"],
                     "admit_seq": self._admit_seq}
            self._admit_seq += 1
            self._slots[slot] = state
            self._last_tok[slot] = tok
            self._token_dev = self._token_dev.at[slot].set(tok)
            admitted = True
            if self.guard_mode != "off" and not (
                    np.isfinite(lp) and np.isfinite(lp_ff[0])):
                self._quarantine(slot, "non-finite prefill score")
            elif self._finished(state):
                self._retire(slot)
        if admitted and self.guard_mode != "off":
            self._audit_paging()

    def _prefill_first(self, slot: int, prompt: np.ndarray
                       ) -> Tuple[int, float, Tuple[float, float]]:
        """Prefill ``prompt`` into ``slot``'s pages and return its first
        greedy token with the token's f32 and FF (hi, lo) scores: every
        program is dispatched before the host reads the three in one
        ``device_get``."""
        logits, cache = self.prefill(prompt)
        self.kv.write_prefill(slot, {b: cache["layers"][b][:, 0]
                                     for b in self.kv.bases})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        lp = self._score(logits, tok)
        lph, lpl = self._score_ff(logits, tok)
        with obs_mod.span(self.obs, "serve.prefill.wait"):
            tok, lp, lph, lpl = jax.device_get((tok, lp, lph, lpl))
        return int(tok[0]), float(lp[0]), (float(lph[0]), float(lpl[0]))

    def _finished(self, state: Dict[str, Any]) -> bool:
        if len(state["tokens"]) >= state["req"].max_new:
            return True
        return self.eos_id is not None and state["tokens"][-1] == self.eos_id

    def _retire(self, slot: int, status: str = OK, detail: str = "") -> None:
        state = self._slots[slot]
        req = state["req"]
        self._set_result(GenResult(
            uid=req.uid,
            tokens=np.asarray(state["tokens"], np.int32),
            logprobs=np.asarray(state["logprobs"], np.float32),
            logprobs_ff=np.asarray(state["logprobs_ff"], np.float32),
            prompt_len=state["prompt_len"],
            status=status, detail=detail))
        self.kv.free_slot(slot)
        self._slots[slot] = None
        self._last_tok[slot] = 0

    def _fast_policy(self) -> PrecisionPolicy:
        """One accuracy class below the serving policy: fast f32
        attention, builtin transcendentals, f32 scoring inputs."""
        return dataclasses.replace(
            self.policy, attention="fast", ff_math=False)

    def _quarantine(self, slot: int, why: str,
                    trust_pages: bool = True) -> None:
        """Evict a poisoned row and retry the whole request on the fast
        tier (greedy decoding is deterministic, so the retry IS the
        request's fast-class answer, not a different sample).  Healthy
        retry => ``DEGRADED``; a retry that still scores non-finite =>
        ``FAILED`` (tokens withheld — never silently wrong)."""
        state = self._slots[slot]
        req = state["req"]
        if trust_pages:
            self.kv.free_slot(slot)
        else:
            self.kv.drop_slot(slot)     # caller rebuilds the free list
        self._slots[slot] = None
        self._last_tok[slot] = 0
        self.guard_stats["quarantined"] += 1
        self.obs.trace.instant("quarantine",
                               args={"uid": int(req.uid), "why": why})
        report_violation("serve.decode", "nonfinite")
        detail = f"guard: {why}; retried on the fast tier"
        try:
            toks, lps = greedy_generate(
                self._weights(), self.cfg, jnp.asarray(req.prompt[None]),
                req.max_new, cache_len=state["prompt_len"] + req.max_new,
                policy=self._fast_policy(), return_logprobs=True,
                eos_id=self.eos_id)
            toks = np.asarray(toks[0], np.int32)
            lps = np.asarray(lps[0], np.float32)
        except Exception as e:   # a retry must never take the engine down
            self._set_result(_empty_result(
                req, FAILED, f"guard: {why}; fast-tier retry raised "
                f"{type(e).__name__}: {e}"))
            return
        if not np.all(np.isfinite(lps)):
            self._set_result(_empty_result(
                req, FAILED, f"guard: {why}; fast-tier retry still "
                f"non-finite"))
            return
        self._set_result(GenResult(
            uid=req.uid, tokens=toks, logprobs=lps,
            logprobs_ff=np.stack([lps, np.zeros_like(lps)], axis=1),
            prompt_len=state["prompt_len"], status=DEGRADED, detail=detail))

    def _audit_paging(self) -> None:
        """Guard-mode integrity audit of the paging metadata: quarantine
        every slot with an untrusted page list, then rebuild the free
        list.  Never raises; runs per flush and per admission round."""
        if self._auditing:
            return
        self._auditing = True
        try:
            problems, bad = self.kv.check_integrity()
            if not problems:
                return
            warnings.warn("ServeEngine: paging metadata corrupt — " +
                          "; ".join(problems[:4]) +
                          (f" (+{len(problems) - 4} more)"
                           if len(problems) > 4 else ""),
                          FFGuardWarning, stacklevel=2)
            report_violation("serve.paging", "nonfinite", len(problems))
            self._flush()
            for slot in sorted(bad):
                if self._slots[slot] is not None:
                    self._quarantine(slot, "corrupt block table",
                                     trust_pages=False)
                else:
                    self.kv.drop_slot(slot)
            self.kv.rebuild_free_list()
            self.guard_stats["integrity_rebuilds"] += 1
            self.obs.trace.instant("integrity_rebuild",
                                   args={"problems": len(problems)})
        finally:
            self._auditing = False

    # -- decode ------------------------------------------------------------

    def _row_len(self, state: Dict[str, Any]) -> int:
        """Tokens already cached for this row = prompt + emitted (incl.
        unsynced pending steps) - 1 (the latest token is the step INPUT —
        its K/V is written by the step itself)."""
        return state["prompt_len"] + len(state["tokens"]) \
            + state["pending"] - 1

    def _preempt(self, slot: int) -> None:
        """Preempt a running row: pages back to the free list, request
        back to the FRONT of the queue (it keeps its original submit
        deadline) — the later re-prefill replays deterministically, so
        the final tokens are identical to an uninterrupted run."""
        state = self._slots[slot]
        req = state["req"]
        self.kv.free_slot(slot)
        self._slots[slot] = None
        self._last_tok[slot] = 0
        self.guard_stats["preempted"] += 1
        self.obs.trace.instant("preempt", args={"uid": int(req.uid)})
        tr = self._req_trace.get(req.uid)
        if tr is not None:
            tr["admit"] = None          # decode restarts at re-admission
        self.queue.insert(0, {"req": req, "t_sub": state["t_sub"],
                              "step_sub": state["step_sub"]})

    def _ensure_growth(self) -> bool:
        """``reserve="prompt"`` only: make sure every active row has a
        page for the K/V it writes this step, preempting the youngest
        running row on pool exhaustion.  Returns False when nothing is
        left to decode (everything preempted/retired)."""
        if self.reserve == "trajectory":
            return any(s is not None for s in self._slots)
        order = sorted(
            (i for i, s in enumerate(self._slots) if s is not None),
            key=lambda i: self._slots[i]["admit_seq"])
        for slot in order:
            state = self._slots[slot]
            if state is None:       # preempted by an older row's growth
                continue
            target = self._row_len(state) + 1
            while True:
                if self.kv.pages_for(target) <= self.kv.pages_for(
                        int(self.kv.seq_lens[slot])) or self.kv.free_pages:
                    self.kv.grow(slot, target)
                    break
                # pool dry: sync pending work, then preempt the youngest
                self._flush()
                if self._slots[slot] is None:   # flush retired/quarantined
                    break
                running = [i for i, s in enumerate(self._slots)
                           if s is not None]
                if len(running) == 1:
                    # nobody to steal from: the pool cannot hold even one
                    # trajectory -> terminal, not a livelock
                    self._retire(slot, FAILED,
                                 "page pool too small for one trajectory")
                    break
                victim = max(running,
                             key=lambda i: self._slots[i]["admit_seq"])
                self._preempt(victim)
                if victim == slot:
                    break
        return any(s is not None for s in self._slots)

    def _step_decode(self) -> None:
        with obs_mod.span(self.obs, "serve.schedule"):
            if not self._ensure_growth():
                return
        with obs_mod.span(self.obs, "serve.decode_prep"):
            active_np = np.asarray([s is not None for s in self._slots])
            lens = np.asarray(
                [self._row_len(s) if s else 0 for s in self._slots],
                np.int32)
            args = (self._weights(), self._token_dev[:, None],
                    jnp.asarray(lens), jnp.asarray(self.kv.block_table),
                    jnp.asarray(active_np), self.kv.planes)
        with obs_mod.span(self.obs, "serve.decode_step"):
            out = self._decode(*args)
        nxt, lp, lph, lpl, bad, self.kv.planes = out[:6]
        pairs = out[6] if len(out) > 6 else None
        if not self._decode_built:
            self._decode_built = True
            # the program just run, from jit's cache: no second compile
            self._program_built(
                "jit_step_decode",
                compiled_text=lambda: self._decode.lower(
                    *args).compile().as_text())
        self._token_dev = nxt
        self._pending.append({"step": self.decode_steps, "nxt": nxt,
                              "lp": lp, "lph": lph, "lpl": lpl,
                              "bad": bad, "pairs": pairs})
        self.decode_steps += 1
        for slot, state in enumerate(self._slots):
            if state is None:
                continue
            state["pending"] += 1
            # the step wrote this row's K/V at position lens[slot] (in
            # prompt mode grow() already advanced seq_lens pre-step)
            self.kv.seq_lens[slot] = int(lens[slot]) + 1

    def _flush(self) -> None:
        """Sync every pending decode step's four (B,) vectors (+ guard
        flag) to the host in ONE ``device_get``, append tokens/scores in
        step order, then apply guard / deadline / finish transitions."""
        if not self._pending:
            return
        entries = self._pending
        self._pending = []
        with obs_mod.span(self.obs, "serve.flush", steps=len(entries)):
            self._apply_synced(entries)

    def _apply_synced(self, entries: List[Dict[str, Any]]) -> None:
        t0 = self.obs.trace.now()
        with obs_mod.span(self.obs, "serve.flush.wait"):
            host = jax.device_get([(e["nxt"], e["lp"], e["lph"], e["lpl"],
                                    e["bad"], e["pairs"]) for e in entries])
        # host time blocked on the device (and the copies back)
        self.obs.registry.histogram("serve_flush_seconds").observe(
            (self.obs.trace.now() - t0) / 1e6)
        flagged: Dict[int, bool] = {}
        for (e, (nxt, lp, lph, lpl, bad, pairs)) in zip(entries, host):
            if pairs is not None:
                self._record_pairs(e["step"], np.asarray(pairs))
            nxt = np.asarray(nxt, np.int32)
            for slot, state in enumerate(self._slots):
                if state is None or state["pending"] == 0:
                    continue
                if state["start_step"] > e["step"]:
                    continue            # admitted after this step ran
                tok = int(nxt[slot])
                state["tokens"].append(tok)
                state["logprobs"].append(float(lp[slot]))
                state["logprobs_ff"].append(
                    (float(lph[slot]), float(lpl[slot])))
                state["pending"] -= 1
                self._last_tok[slot] = tok
                if bool(bad[slot]):
                    flagged[slot] = True
        if flagged:
            self.guard_stats["flagged_rows"] += len(flagged)
        for slot in list(flagged):
            if self._slots[slot] is not None:
                self._quarantine(slot, "per-step probe flagged the row")
        for slot, state in enumerate(self._slots):
            if state is None:
                continue
            if self._deadline_passed(state["req"], state["t_sub"],
                                     state["step_sub"]):
                self._retire(slot, TIMEOUT,
                             "deadline expired mid-decode "
                             f"(kept {len(state['tokens'])} tokens)")
            elif self._finished(state):
                self._retire(slot)
        if self.guard_mode != "off":
            self._audit_paging()

    def _record_pairs(self, step: int, pairs: np.ndarray) -> None:
        """One decode step's (token, expert) pairs routed to each held
        expert of each MoE layer, (L_moe, held): a ``moe`` counter event
        (``pairs`` in all, ``hit`` the (layer, expert) cells with any,
        ``max`` the fullest cell, and the ``layers`` and ``held`` it
        spans) and the ``serve_moe_pairs_total`` counter."""
        self.obs.trace.counter("moe", {
            "step": step, "pairs": int(pairs.sum()),
            "hit": int((pairs > 0).sum()), "max": int(pairs.max()),
            "layers": pairs.shape[0], "held": pairs.shape[1]})
        self.obs.registry.counter("serve_moe_pairs_total").inc(
            int(pairs.sum()))

    def _must_flush(self) -> bool:

        if not self._pending:
            return False
        if len(self._pending) >= self.sync_every:
            return True
        for state in self._slots:
            if state is None:
                continue
            req = state["req"]
            if len(state["tokens"]) + state["pending"] >= req.max_new:
                return True
            if req.deadline_s is not None or req.deadline_steps is not None:
                if self._deadline_passed(req, state["t_sub"],
                                         state["step_sub"]):
                    return True
        if self.queue and any(s is None for s in self._slots):
            return True                 # admission opportunity
        return False

    def step(self) -> bool:
        """One scheduler iteration: admit waiting requests into free rows,
        then advance every running row one token.  Returns True while work
        remains.  Public hook for callers that interleave ``submit`` with
        decoding (staggered arrivals join the running batch at the next
        step — see ``examples/serve_lm.py``).  Never raises for
        off-nominal scheduling conditions — every request ends in a
        terminal status from :data:`STATUSES`.

        Under ``obs.enable()`` the step is a ``serve.step`` span whose
        children name its phases (``serve.schedule``, ``serve.admit`` >
        ``serve.prefill`` > ``serve.prefill.wait``, ``serve.decode_prep``,
        ``serve.decode_step``, ``serve.flush`` > ``serve.flush.wait``)."""
        with obs_mod.span(self.obs, "serve.step"):
            return self._step()

    def _step(self) -> bool:
        with obs_mod.span(self.obs, "serve.schedule"):
            self._expire_queue()
        self._admit()
        if any(s is not None for s in self._slots):
            self._step_decode()
            if self._must_flush():
                self._flush()
                self._admit()
        elif self.queue:
            self._flush()
            if not any(s is not None for s in self._slots) and self.queue:
                # empty engine, head still unschedulable: terminal (pages
                # leaked or pool undersized) — fail it rather than stall
                q = self.queue.pop(0)
                self._set_result(_empty_result(
                    q["req"], FAILED,
                    "unschedulable: no running rows and the head request "
                    "cannot be admitted"))
        elif self._pending:
            self._flush()
        self._trace_step_counters()
        return (any(s is not None for s in self._slots)
                or bool(self.queue) or bool(self._pending))

    def _trace_step_counters(self) -> None:
        """Per-scheduler-step samples: queue depth, active batch rows, and
        page-pool occupancy, as both registry gauges and Perfetto counter
        tracks."""
        depth = len(self.queue)
        active = sum(1 for s in self._slots if s is not None)
        free = len(self.kv.free_pages)
        used = self.kv.num_pages - free
        self.obs.registry.gauge("serve_queue_depth").set(depth)
        self.obs.registry.gauge("serve_active_rows").set(active)
        self.obs.registry.gauge("serve_pages_used").set(used)
        self.obs.trace.counter("queue", {"depth": depth, "active": active})
        self.obs.trace.counter("pages", {"used": used, "free": free})

    def run(self, *, snapshot_dir: Optional[str] = None,
            snapshot_every: Optional[int] = None) -> Dict[int, GenResult]:
        """Drain the queue: admit + decode until everything completes.
        Every submitted uid is present in the result dict with a terminal
        status — under fault injection too (chaos tier, see
        ``repro.chaos``).

        With ``snapshot_dir`` + ``snapshot_every`` set, the engine
        snapshots every N decode steps through an async checkpointer
        (writes overlap decode), ``poll()``-ing it each scheduler
        iteration so a failing disk surfaces immediately as an
        :class:`FFGuardWarning` + ``guard_stats["snapshot_errors"]``
        (serving continues — durability degrades, decode does not).  A
        final synchronous snapshot lands after the queue drains."""
        ckpt = None
        last_snap = self.decode_steps
        if snapshot_dir is not None and snapshot_every:
            from repro.checkpoint import checkpoint as ckpt_lib
            ckpt = ckpt_lib.AsyncCheckpointer(snapshot_dir)
        while self.step():
            if ckpt is not None:
                self._poll_snapshot(ckpt)
                if self.decode_steps - last_snap >= snapshot_every:
                    try:
                        arrays, meta = self.snapshot()
                        ckpt.save(self.decode_steps, arrays, extra=meta)
                        self._snap_cover = set(self.results)
                        self.obs.trace.instant(
                            "snapshot", args={"step": self.decode_steps,
                                              "mode": "async"})
                    except Exception as e:
                        self._snapshot_error(e)
                    last_snap = self.decode_steps
        self._flush()
        if ckpt is not None:
            try:
                ckpt.wait()
            except BaseException as e:
                self._snapshot_error(e)
            self._snap_cover = None
            try:
                self.save_snapshot(snapshot_dir)
            except Exception as e:
                self._snapshot_error(e)
        return self.results

    # -- crash safety: snapshot / restore / journal ------------------------

    def _fingerprint(self) -> Dict[str, Any]:
        """The construction-time knobs a snapshot is only valid under.
        Model params/config are NOT snapshotted (the caller provides the
        same weights, as with trainer checkpoints) — the policy repr and
        config name are fingerprinted so a mismatch fails loudly."""
        return {"kv_mode": self.kv.kv_mode, "max_batch": self.max_batch,
                "page_size": self.kv.page_size,
                "max_ctx": self.kv.max_pages * self.kv.page_size,
                "num_pages": self.kv.num_pages, "eos_id": self.eos_id,
                "reserve": self.reserve, "sync_every": self.sync_every,
                "guard": self.guard_mode, "max_queue": self.max_queue,
                "policy_repr": repr(self.policy),
                "cfg_name": self.cfg.name}

    def snapshot(self):
        """Freeze the full engine between decode steps.  Returns
        ``(arrays, meta)``: a flat ``{name: np.ndarray}`` dict (KV planes
        via :meth:`PagedKVCache.to_state`, per-slot/queue prompts and
        emitted tokens/scores, completed results) plus a JSON-able meta
        dict (schema, wall time, counters, per-request deadlines as
        elapsed time — portable across processes).  Pending device work
        is synced first, so the snapshot sits at a step boundary and the
        resumed decode replays token-for-token."""
        self._flush()
        arrays: Dict[str, np.ndarray] = {}
        for k, v in self.kv.to_state().items():
            arrays[f"kv.{k}"] = v
        arrays["last_tok"] = self._last_tok.copy()
        now_m, now_w = time.monotonic(), time.time()
        slots_meta: List[Optional[Dict[str, Any]]] = []
        for i, s in enumerate(self._slots):
            if s is None:
                slots_meta.append(None)
                continue
            req = s["req"]
            arrays[f"slot.{i}.prompt"] = np.asarray(req.prompt, np.int32)
            arrays[f"slot.{i}.tokens"] = np.asarray(s["tokens"], np.int32)
            arrays[f"slot.{i}.logprobs"] = np.asarray(
                s["logprobs"], np.float32)
            arrays[f"slot.{i}.logprobs_ff"] = np.asarray(
                s["logprobs_ff"], np.float32).reshape(-1, 2)
            slots_meta.append({
                "uid": int(req.uid), "max_new": int(req.max_new),
                "deadline_s": req.deadline_s,
                "deadline_steps": req.deadline_steps,
                "prompt_len": int(s["prompt_len"]),
                "start_step": int(s["start_step"]),
                "step_sub": int(s["step_sub"]),
                "admit_seq": int(s["admit_seq"]),
                "elapsed_s": float(now_m - s["t_sub"])})
        queue_meta = []
        for j, q in enumerate(self.queue):
            req = q["req"]
            arrays[f"queue.{j}.prompt"] = np.asarray(req.prompt, np.int32)
            queue_meta.append({
                "uid": int(req.uid), "max_new": int(req.max_new),
                "deadline_s": req.deadline_s,
                "deadline_steps": req.deadline_steps,
                "step_sub": int(q["step_sub"]),
                "elapsed_s": float(now_m - q["t_sub"])})
        results_meta = []
        for uid, r in self.results.items():
            arrays[f"result.{uid}.tokens"] = np.asarray(r.tokens, np.int32)
            arrays[f"result.{uid}.logprobs"] = np.asarray(
                r.logprobs, np.float32)
            arrays[f"result.{uid}.logprobs_ff"] = np.asarray(
                r.logprobs_ff, np.float32).reshape(-1, 2)
            results_meta.append({"uid": int(uid), "status": r.status,
                                 "detail": r.detail,
                                 "prompt_len": int(r.prompt_len)})
        meta = {"schema": SNAPSHOT_SCHEMA, "wall_time": now_w,
                "decode_steps": int(self.decode_steps),
                "admit_seq": int(self._admit_seq),
                "guard_stats": {k: int(v)
                                for k, v in self.guard_stats.items()},
                "engine": self._fingerprint(),
                "slots": slots_meta, "queue": queue_meta,
                "results": results_meta}
        return arrays, meta

    def restore(self, arrays: Dict[str, np.ndarray], meta: Dict[str, Any],
                *, downtime_s: Optional[float] = None) -> None:
        """Rebuild a freshly constructed engine from :meth:`snapshot`
        output.  Validates the snapshot schema and the engine
        fingerprint (kv_mode, geometry, policy, ...) — a mismatch raises
        ``ValueError`` rather than decoding subtly-wrong tokens.

        Deadlines: per-request elapsed time was stored relative to the
        snapshot's wall clock; ``downtime_s`` (default: wall time since
        the snapshot) is added back, so a wall-clock ``deadline_s`` that
        expired while the process was down retires as the documented
        ``TIMEOUT`` immediately — never silently revived.  Deterministic
        ``deadline_steps`` budgets count decode steps and are unaffected
        by downtime."""
        if not isinstance(meta, dict) or meta.get("schema") != \
                SNAPSHOT_SCHEMA:
            raise ValueError(
                f"engine snapshot schema {meta.get('schema')!r} != "
                f"supported {SNAPSHOT_SCHEMA}")
        mine, theirs = self._fingerprint(), meta["engine"]
        for k in sorted(set(mine) | set(theirs)):
            if mine.get(k) != theirs.get(k):
                raise ValueError(
                    f"snapshot/engine mismatch: snapshot has "
                    f"{k}={theirs.get(k)!r}, this engine has "
                    f"{mine.get(k)!r}")
        if (self.queue or self._pending or self.results
                or any(s is not None for s in self._slots)):
            raise RuntimeError("restore() requires a freshly constructed "
                               "engine (no queued/running/completed work)")
        self.kv = PagedKVCache.from_state(
            {k[len("kv."):]: np.asarray(v) for k, v in arrays.items()
             if k.startswith("kv.")})
        self.decode_steps = int(meta["decode_steps"])
        self._admit_seq = int(meta["admit_seq"])
        self.guard_stats.update(meta["guard_stats"])
        now_m, now_w = time.monotonic(), time.time()
        if downtime_s is None:
            downtime_s = max(0.0, now_w - float(meta["wall_time"]))
        self._last_tok = np.asarray(arrays["last_tok"], np.int32).copy()
        self._token_dev = jnp.asarray(self._last_tok)
        for i, sm in enumerate(meta["slots"]):
            if sm is None:
                continue
            req = Request(uid=sm["uid"],
                          prompt=np.asarray(arrays[f"slot.{i}.prompt"],
                                            np.int32),
                          max_new=sm["max_new"],
                          deadline_s=sm["deadline_s"],
                          deadline_steps=sm["deadline_steps"])
            lf = np.asarray(arrays[f"slot.{i}.logprobs_ff"],
                            np.float32).reshape(-1, 2)
            self._slots[i] = {
                "req": req, "prompt_len": sm["prompt_len"],
                "tokens": [int(t) for t in arrays[f"slot.{i}.tokens"]],
                "logprobs": [float(x)
                             for x in arrays[f"slot.{i}.logprobs"]],
                "logprobs_ff": [(float(h), float(l)) for h, l in lf],
                "pending": 0, "start_step": sm["start_step"],
                "t_sub": now_m - (sm["elapsed_s"] + downtime_s),
                "step_sub": sm["step_sub"], "admit_seq": sm["admit_seq"]}
            # reopen the restored request's trace timeline (the pre-crash
            # spans belong to the crashed process's trace)
            self._trace_submit(sm["uid"])
            self._req_trace[sm["uid"]]["admit"] = self.obs.trace.now()
        self.queue = []
        for j, qm in enumerate(meta["queue"]):
            req = Request(uid=qm["uid"],
                          prompt=np.asarray(arrays[f"queue.{j}.prompt"],
                                            np.int32),
                          max_new=qm["max_new"],
                          deadline_s=qm["deadline_s"],
                          deadline_steps=qm["deadline_steps"])
            self.queue.append({
                "req": req,
                "t_sub": now_m - (qm["elapsed_s"] + downtime_s),
                "step_sub": qm["step_sub"]})
            self._trace_submit(qm["uid"])
        for rm in meta["results"]:
            uid = rm["uid"]
            self.results[uid] = GenResult(
                uid=uid,
                tokens=np.asarray(arrays[f"result.{uid}.tokens"],
                                  np.int32),
                logprobs=np.asarray(arrays[f"result.{uid}.logprobs"],
                                    np.float32),
                logprobs_ff=np.asarray(
                    arrays[f"result.{uid}.logprobs_ff"],
                    np.float32).reshape(-1, 2),
                prompt_len=rm["prompt_len"], status=rm["status"],
                detail=rm["detail"])
        # wall-clock deadlines that expired during downtime retire NOW,
        # with the tokens produced so far — documented, never revived
        self._expire_queue()
        for slot, state in enumerate(self._slots):
            if state is not None and self._deadline_passed(
                    state["req"], state["t_sub"], state["step_sub"]):
                self._retire(slot, TIMEOUT,
                             "deadline expired across restart downtime "
                             f"(kept {len(state['tokens'])} tokens)")

    def save_snapshot(self, directory: str) -> str:
        """Synchronous :meth:`snapshot` -> hardened checkpoint write
        (atomic tmp+rename, CRC32 manifest, keep-last-3).  The journal is
        compacted afterwards: the durable snapshot now covers every
        completed result.  Returns the checkpoint path."""
        from repro.checkpoint import checkpoint as ckpt_lib
        arrays, meta = self.snapshot()
        path = ckpt_lib.save(directory, self.decode_steps, arrays,
                             extra=meta)
        self.obs.trace.instant("snapshot",
                               args={"step": self.decode_steps,
                                     "mode": "sync"})
        self.obs.registry.counter("serve_snapshots_total").inc()
        if self.journal is not None:
            self.journal.compact(set(self.results))
        return path

    def _poll_snapshot(self, ckpt) -> None:
        """Surface async-write errors into the engine loop (not just the
        next ``wait()``), and compact the journal once the last enqueued
        snapshot is durably on disk."""
        err = ckpt.poll()
        if err is not None:
            self._snapshot_error(err)
            self._snap_cover = None
        elif self._snap_cover is not None and not (
                ckpt._thread is not None and ckpt._thread.is_alive()):
            if self.journal is not None:
                self.journal.compact(self._snap_cover)
            self._snap_cover = None

    def _snapshot_error(self, err: BaseException) -> None:
        self.guard_stats["snapshot_errors"] += 1
        self.obs.trace.instant("snapshot_error",
                               args={"error": type(err).__name__})
        warnings.warn(
            f"ServeEngine: snapshot write failed "
            f"({type(err).__name__}: {err}) — serving continues, restart "
            f"durability degraded", FFGuardWarning, stacklevel=3)

    def attach_journal(self, path: str) -> RequestJournal:
        """Attach a write-ahead request journal, replaying any journaled
        request not accounted for by the current engine state (terminal
        result, running row, or queued) in original submission order.
        Greedy decoding is deterministic, so a replayed request produces
        the same tokens the crashed run would have."""
        self.journal = RequestJournal(path)
        now_w = time.time()
        for rec in self.journal.pending():
            uid = rec["uid"]
            if uid in self.results:
                continue
            if any(s is not None and s["req"].uid == uid
                   for s in self._slots):
                continue
            if any(q["req"].uid == uid for q in self.queue):
                continue
            req = Request(uid=uid,
                          prompt=np.asarray(rec["prompt"], np.int32),
                          max_new=rec["max_new"],
                          deadline_s=rec.get("deadline_s"),
                          deadline_steps=rec.get("deadline_steps"))
            elapsed = max(0.0, now_w - rec.get("t_wall", now_w))
            self._submit(req, t_sub=time.monotonic() - elapsed,
                         step_sub=min(int(rec.get("step_sub", 0)),
                                      self.decode_steps),
                         bounded=False)
        return self.journal

    # -- guard introspection ----------------------------------------------

    def probe_kv(self):
        """Whole-pool FF health probe of the live KV planes: one
        :class:`~repro.ff.guard.GuardCounts` over every plane (in
        ``ff_bf16`` mode the storage limbs are merged first — bf16 limb
        pairs have their own, coarser normalization scale).  Debug /
        chaos-harness hook; the per-step probe only sees NEW K/V."""
        from repro.ff.guard import GuardCounts, guard_probe
        tot = [0, 0, 0]
        for base in self.kv.bases:
            if self.kv.kv_mode == "ff_bf16":
                plane = ff_merge(self.kv.planes[f"{base}_hi"],
                                 self.kv.planes[f"{base}_lo"])
            else:
                plane = self.kv.planes[base].astype(jnp.float32)
            c = guard_probe(plane)
            tot = [t + int(v) for t, v in zip(tot, c)]
        return GuardCounts(*(jnp.int32(t) for t in tot))


def resume_engine(params: Any, cfg: ModelConfig, snapshot_dir: str, *,
                  journal: Optional[str] = None,
                  downtime_s: Optional[float] = None,
                  policy: Optional[PrecisionPolicy] = None,
                  **engine_kwargs) -> ServeEngine:
    """Warm-restart a :class:`ServeEngine` after a crash.

    Loads the newest snapshot generation that VERIFIES (per-leaf CRC32 +
    schema version; a corrupted/torn/stale generation falls back warned
    to the previous retained one — see ``repro.checkpoint``), constructs
    an engine with the snapshot's own knobs (overridable via
    ``engine_kwargs``), restores it, then replays the write-ahead
    ``journal``'s unaccounted-for requests in original order.  When NO
    snapshot exists at all (crash before the first write, or its tmp dir
    was garbage-collected) the ladder bottoms out at a cold engine +
    full WAL replay — still token-for-token the lost run, just without
    the saved KV work.  Continuing is exact replay: greedy decode is
    deterministic, so tokens (and FF logprob bits) match the
    uninterrupted run; wall-clock deadlines that expired during downtime
    retire as ``TIMEOUT`` on restore.  Raises
    :class:`repro.checkpoint.checkpoint.CheckpointError` when
    generations exist but none verifies (corruption is never silent)."""
    from repro.checkpoint import checkpoint as ckpt_lib
    try:
        arrays, _step, meta = ckpt_lib.load_dict(snapshot_dir)
    except FileNotFoundError:
        arrays, meta = None, None
    if meta is not None:
        knobs = {k: v for k, v in meta["engine"].items()
                 if k not in ("policy_repr", "cfg_name", "guard")}
        knobs["guard"] = meta["engine"].get("guard", "off")
        knobs.update(engine_kwargs)
        eng = ServeEngine(params, cfg, policy=policy, **knobs)
        eng.restore(arrays, meta, downtime_s=downtime_s)
    else:
        eng = ServeEngine(params, cfg, policy=policy, **engine_kwargs)
    if journal is not None:
        eng.attach_journal(journal)
    return eng
