"""Beyond-paper table: continuous-batching serving vs sequential decode.

The serving subsystem (``repro.serve``) wraps the fused FF flash-attention
op in a production decode loop: paged FF KV cache, continuous batching
(join/evict between decode steps), and FF ``token_logprob`` scoring as the
accuracy-critical tier.  This table measures the two claims the subsystem
ships with:

  throughput — tokens/sec over a fixed mixed-length request set:
    arm ``greedy``     — the literal sequential baseline: one
                         :func:`repro.train.serve_step.greedy_generate`
                         call per request, as a library user would write
                         it (each call builds fresh jit closures, so the
                         per-request retrace cost is part of the arm —
                         that IS the naive cost).  The >=3x gate compares
                         against this arm.
    arm ``sequential_warm`` — honesty row: the same sequential loop with
                         the prefill/decode jits built ONCE and reused,
                         i.e. the best a batch-of-1 loop can do.  The
                         engine's speedup vs this arm is the part that
                         comes from batching rather than from caching.
    arm ``engine B=k`` — :class:`repro.serve.ServeEngine` at batch k,
                         timed on a warmed instance (page-parity
                         ``kv_mode="bf16"`` plus one f32-page row).

  accuracy — every engine token is scored by ``token_logprob_ff`` (full
    vocab-LSE chain in float-float).  The gate recomputes each score from
    the engine's own logits path with a numpy f64 oracle and requires the
    worst relative error <= 2^-40 (the f32-returning score floors at
    ~2^-24 — recorded alongside for contrast).  Token parity vs the
    greedy baseline is asserted for every request.

Modes:
  python -m benchmarks.table_serving            # full table (16 requests)
  python -m benchmarks.table_serving --quick    # CI: 8 requests, B in {2,8}
  python -m benchmarks.table_serving --guard-overhead
      # additionally gate the ff.guard(mode="check") probe cost at B=8:
      # min-of-3 paired runs vs guard="off", <= 5% tokens/s overhead
  python -m benchmarks.table_serving --snapshot-overhead
      # additionally gate the crash-safety cost at B=8: engine with a
      # write-ahead journal + async snapshot every 8 decode steps vs the
      # same engine with durability off, min-of-3 paired runs, <= 5%
      # tokens/s overhead; also measures restore_to_first_token_s (warm
      # restart from the snapshot until the first post-restore token is
      # synced — includes jit re-compile, the honest restart cost)
  python -m benchmarks.table_serving --obs-overhead
      # additionally gate the observability cost at B=8: engine with the
      # full repro.obs stack on (per-request Chrome spans, latency
      # histograms, per-step gauges, obs.enable() profiler annotations)
      # vs the default engine, min-of-3 paired runs, <= 5% tokens/s
      # overhead (docs/DESIGN_observability.md)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_cpu_max_isa" not in _flags:
    os.environ["XLA_FLAGS"] = ("--xla_cpu_max_isa=SSE4_2 " + _flags).strip()

import numpy as np
import jax
import jax.numpy as jnp

import repro.ff as ff
from repro.models import init_cache, init_params
from repro.models.config import ModelConfig
from repro.train.serve_step import (greedy_generate, make_decode_step,
                                    make_prefill_step, token_logprob,
                                    token_logprob_ff)
from repro.serve import Request, ServeEngine

#: serving accuracy contract: FF token logprob vs the f64 oracle
LOGPROB_TOL = 2.0 ** -40
#: throughput contract: engine at batch>=8 vs the sequential greedy arm
SPEEDUP_GATE = 3.0
GATE_BATCH = 8
#: robustness contract: ff.guard(mode="check") probe overhead at B=8
#: (docs/DESIGN_robustness.md §5) — <= 5% tokens/s vs guard="off"
GUARD_OVERHEAD_GATE = 1.05
#: crash-safety contract: WAL + async snapshot every SNAPSHOT_EVERY decode
#: steps at B=8 (docs/DESIGN_robustness.md §6) — <= 5% tokens/s vs off
SNAPSHOT_OVERHEAD_GATE = 1.05
SNAPSHOT_EVERY = 8
#: observability contract: full repro.obs instrumentation at B=8
#: (docs/DESIGN_observability.md §5) — <= 5% tokens/s vs obs off
OBS_OVERHEAD_GATE = 1.05

BENCH_CFG = dict(name="serve-bench", family="dense", num_layers=4,
                 d_model=256, num_heads=8, num_kv_heads=4, d_ff=1024,
                 vocab_size=4096, max_seq_len=128, compute_dtype="float32")


def _requests(rng: np.random.Generator, n: int, max_new: int,
              vocab: int) -> List[Request]:
    lens = rng.integers(8, 49, size=n)
    return [Request(uid=i,
                    prompt=rng.integers(1, vocab, size=int(l)).astype(np.int32),
                    max_new=max_new)
            for i, l in enumerate(lens)]


# --------------------------------------------------------------------------
# arms
# --------------------------------------------------------------------------

def _run_greedy(params, cfg, reqs, cache_len) -> Dict:
    """One greedy_generate call per request — fresh jit closures per call
    (the naive sequential cost a library user pays)."""
    outs = {}
    t0 = time.perf_counter()
    for r in reqs:
        toks = greedy_generate(params, cfg, jnp.asarray(r.prompt[None]),
                               r.max_new, cache_len)
        outs[r.uid] = np.asarray(toks[0])
    dt = time.perf_counter() - t0
    return {"tokens": outs, "seconds": dt,
            "count": sum(len(t) for t in outs.values())}


def _run_sequential_warm(params, cfg, reqs, cache_len) -> Dict:
    """Sequential loop with the prefill/decode jits built once."""
    pf = jax.jit(make_prefill_step(cfg))
    dc = jax.jit(make_decode_step(cfg))

    def one(r: Request) -> np.ndarray:
        cache = init_cache(cfg, 1, cache_len)
        logits, cache = pf(params, {"tokens": jnp.asarray(r.prompt[None])},
                           cache)
        toks = [jnp.argmax(logits, -1).astype(jnp.int32)]
        for t in range(r.max_new - 1):
            logits, cache = dc(params, toks[-1][:, None],
                               jnp.int32(len(r.prompt) + t), cache)
            toks.append(jnp.argmax(logits, -1).astype(jnp.int32))
        jax.block_until_ready(toks[-1])
        return np.asarray(jnp.concatenate(toks))

    for r in reqs:        # compile every prompt-length's prefill off-clock
        one(r)
    t0 = time.perf_counter()
    outs = {r.uid: one(r) for r in reqs}
    dt = time.perf_counter() - t0
    return {"tokens": outs, "seconds": dt,
            "count": sum(len(t) for t in outs.values())}


def _run_engine(params, cfg, reqs, *, batch, cache_len, kv_mode,
                guard: str = "off", snapshot_dir: Optional[str] = None,
                snapshot_every: Optional[int] = None,
                instrument: bool = False) -> Dict:
    journal = (os.path.join(snapshot_dir, "wal.jsonl")
               if snapshot_dir else None)
    kwargs = {}
    if instrument:
        from repro import obs
        kwargs["obs"] = obs.Observer()
    eng = ServeEngine(params, cfg, max_batch=batch, page_size=16,
                      max_ctx=cache_len, kv_mode=kv_mode, guard=guard,
                      journal=journal, **kwargs)
    for r in reqs:
        eng.submit(r)
    eng.run()                                      # compile outside the clock
    eng.results = {}
    for r in reqs:
        eng.submit(r)
    if instrument:
        from repro import obs
        with obs.enable():       # profiler annotations on, like production
            t0 = time.perf_counter()
            res = eng.run(snapshot_dir=snapshot_dir,
                          snapshot_every=snapshot_every)
            dt = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        res = eng.run(snapshot_dir=snapshot_dir,
                      snapshot_every=snapshot_every)
        dt = time.perf_counter() - t0
    out = {"tokens": {u: r.tokens for u, r in res.items()},
           "results": res, "seconds": dt,
           "count": sum(len(r.tokens) for r in res.values())}
    if instrument:
        out["observer"] = eng.obs
    return out


# --------------------------------------------------------------------------
# accuracy gate: FF token logprob vs the f64 oracle, on REAL logits
# --------------------------------------------------------------------------

def _logprob_accuracy(params, cfg, reqs, cache_len) -> Dict:
    """Score the first decode logits of each request with both tiers and
    compare against the exact f64 log-softmax oracle."""
    pf = jax.jit(make_prefill_step(cfg))
    worst_ff, worst_f32 = 0.0, 0.0
    for r in reqs:
        cache = init_cache(cfg, 1, cache_len)
        logits, _ = pf(params, {"tokens": jnp.asarray(r.prompt[None])},
                       cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        s_ff = token_logprob_ff(logits, tok)
        s32 = token_logprob(logits, tok)
        lg64 = np.asarray(logits, np.float64)
        rows = np.arange(lg64.shape[0])
        m = lg64.max(-1)
        # log1p of the non-argmax terms: f64 log(1 + r) would round 1 + r
        e = np.exp(lg64 - m[:, None])
        e[rows, lg64.argmax(-1)] = 0.0
        ref = (lg64[rows, np.asarray(tok)] - m) - np.log1p(e.sum(-1))
        got = np.asarray(s_ff.hi, np.float64) + np.asarray(s_ff.lo, np.float64)
        den = np.maximum(np.abs(ref), 1e-30)
        worst_ff = max(worst_ff, float(np.max(np.abs(got - ref) / den)))
        worst_f32 = max(worst_f32, float(np.max(
            np.abs(np.asarray(s32, np.float64) - ref) / den)))
    return {"ff_logprob_max_rel_err": worst_ff,
            "f32_logprob_max_rel_err": worst_f32,
            "tol": LOGPROB_TOL}


# --------------------------------------------------------------------------

def _guard_overhead_arms(params, cfg, reqs, *, batch, cache_len,
                         reps: int) -> tuple:
    """Interleaved min-of-``reps`` timing of guard="off" vs guard="check"
    at the gate batch (bf16 pages).  Interleaving means a load spike hits
    both arms alike; min-of-reps discards one-off stalls."""
    best: Dict[str, Dict] = {}
    for _ in range(max(1, reps)):
        for mode in ("off", "check"):
            r = _run_engine(params, cfg, reqs, batch=batch,
                            cache_len=cache_len, kv_mode="bf16", guard=mode)
            if mode not in best or r["seconds"] < best[mode]["seconds"]:
                best[mode] = r
    return best["off"], best["check"]


def _obs_overhead_arms(params, cfg, reqs, *, batch, cache_len,
                       reps: int) -> tuple:
    """Interleaved min-of-``reps`` timing of the default engine vs one
    with the full observability stack on: a dedicated ``obs.Observer``
    (per-request Chrome spans, latency histograms, per-step gauges) plus
    the ``obs.enable()`` profiler-annotation scope.  Span recording is
    host-side list appends + perf_counter reads per lifecycle event —
    the gate proves that stays under 5% of tokens/s at the gate batch."""
    best: Dict[str, Dict] = {}
    for _ in range(max(1, reps)):
        for mode in ("off", "obs"):
            r = _run_engine(params, cfg, reqs, batch=batch,
                            cache_len=cache_len, kv_mode="bf16",
                            instrument=(mode == "obs"))
            if mode not in best or r["seconds"] < best[mode]["seconds"]:
                best[mode] = r
    return best["off"], best["obs"]


def _snapshot_overhead_arms(params, cfg, reqs, *, batch, cache_len,
                            reps: int) -> tuple:
    """Interleaved min-of-``reps`` timing of durability OFF vs the full
    crash-safety path (fsync'd write-ahead journal + async CRC32'd
    snapshot every SNAPSHOT_EVERY decode steps) at the gate batch.  Each
    snapshot rep writes into a fresh temp directory so retention GC cost
    is identical across reps."""
    import shutil
    import tempfile
    best: Dict[str, Dict] = {}
    for _ in range(max(1, reps)):
        for mode in ("off", "snap"):
            if mode == "snap":
                d = tempfile.mkdtemp(prefix="serve-snap-bench-")
                try:
                    r = _run_engine(params, cfg, reqs, batch=batch,
                                    cache_len=cache_len, kv_mode="bf16",
                                    snapshot_dir=d,
                                    snapshot_every=SNAPSHOT_EVERY)
                finally:
                    shutil.rmtree(d, ignore_errors=True)
            else:
                r = _run_engine(params, cfg, reqs, batch=batch,
                                cache_len=cache_len, kv_mode="bf16")
            if mode not in best or r["seconds"] < best[mode]["seconds"]:
                best[mode] = r
    return best["off"], best["snap"]


def _restore_to_first_token(params, cfg, reqs, *, batch, cache_len) -> float:
    """Warm-restart latency: run a few decode steps, snapshot, then time
    ``resume_engine`` (verified checkpoint load + KV/slot rebuild + jit
    re-compile in the fresh process's stead) until the FIRST post-restore
    token is synced to the host.  Compile cost is deliberately on the
    clock — it IS the restart cost a crashed server pays."""
    import shutil
    import tempfile
    from repro.serve import resume_engine
    d = tempfile.mkdtemp(prefix="serve-restore-bench-")
    try:
        snapdir = os.path.join(d, "snap")
        wal = os.path.join(d, "wal.jsonl")
        eng = ServeEngine(params, cfg, max_batch=batch, page_size=16,
                          max_ctx=cache_len, kv_mode="bf16", journal=wal)
        for r in reqs:
            eng.submit(r)
        for _ in range(6):
            if not eng.step():
                break
        eng.save_snapshot(snapdir)

        def synced(e) -> int:
            return (sum(len(s["tokens"]) for s in e._slots if s is not None)
                    + sum(len(r.tokens) for r in e.results.values()))

        t0 = time.perf_counter()
        eng2 = resume_engine(params, cfg, snapdir, journal=wal,
                             max_batch=batch, max_ctx=cache_len,
                             page_size=16, kv_mode="bf16")
        n0 = synced(eng2)
        while eng2.step():
            eng2._flush()
            if synced(eng2) > n0:
                break
        return time.perf_counter() - t0
    finally:
        shutil.rmtree(d, ignore_errors=True)


def run(*, num_requests: int = 16, max_new: int = 24,
        batches: Sequence[int] = (2, 4, 8), cache_len: int = 80,
        guard_reps: int = 1, snapshot_reps: int = 0, obs_reps: int = 0):
    cfg = ModelConfig(**BENCH_CFG)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = _requests(rng, num_requests, max_new, cfg.vocab_size)

    greedy = _run_greedy(params, cfg, reqs, cache_len)
    warm = _run_sequential_warm(params, cfg, reqs, cache_len)
    tps_greedy = greedy["count"] / greedy["seconds"]
    tps_warm = warm["count"] / warm["seconds"]

    rows: List[Dict] = [
        {"arm": "greedy", "batch": 1, "kv_mode": "bf16",
         "tokens": greedy["count"], "seconds": greedy["seconds"],
         "tokens_per_s": tps_greedy, "speedup_vs_greedy": 1.0,
         "speedup_vs_warm": tps_greedy / tps_warm},
        {"arm": "sequential_warm", "batch": 1, "kv_mode": "bf16",
         "tokens": warm["count"], "seconds": warm["seconds"],
         "tokens_per_s": tps_warm, "speedup_vs_greedy": tps_warm / tps_greedy,
         "speedup_vs_warm": 1.0},
    ]
    parity_failures: List[str] = []
    engine_arms = [(b, "bf16") for b in batches] + [(max(batches), "f32")]
    for batch, kv_mode in engine_arms:
        eng = _run_engine(params, cfg, reqs, batch=batch,
                          cache_len=cache_len, kv_mode=kv_mode)
        tps = eng["count"] / eng["seconds"]
        rows.append({"arm": "engine", "batch": batch, "kv_mode": kv_mode,
                     "tokens": eng["count"], "seconds": eng["seconds"],
                     "tokens_per_s": tps,
                     "speedup_vs_greedy": tps / tps_greedy,
                     "speedup_vs_warm": tps / tps_warm})
        if kv_mode == "bf16":    # page parity mode: token-for-token greedy
            for r in reqs:
                if not np.array_equal(eng["tokens"][r.uid],
                                      greedy["tokens"][r.uid]):
                    parity_failures.append(
                        f"engine B={batch} uid={r.uid}: tokens diverge "
                        f"from greedy_generate")

    # guard-overhead arm: the same B=GATE_BATCH bf16 engine with the
    # per-step health probe compiled in (mode="check" — observe, don't
    # degrade).  Paired min-of-`guard_reps` timing against a fresh
    # guard="off" engine damps scheduler noise for the <=5% gate.
    off_best, guarded = _guard_overhead_arms(
        params, cfg, reqs, batch=max(batches), cache_len=cache_len,
        reps=guard_reps)
    tps_off = off_best["count"] / off_best["seconds"]
    tps_guard = guarded["count"] / guarded["seconds"]
    rows.append({"arm": "engine_guarded", "batch": max(batches),
                 "kv_mode": "bf16", "tokens": guarded["count"],
                 "seconds": guarded["seconds"], "tokens_per_s": tps_guard,
                 "speedup_vs_greedy": tps_guard / tps_greedy,
                 "speedup_vs_warm": tps_guard / tps_warm,
                 "guard_overhead": tps_off / tps_guard})
    for r in reqs:           # check mode must not change a single token
        if not np.array_equal(guarded["tokens"][r.uid],
                              greedy["tokens"][r.uid]):
            parity_failures.append(
                f"engine_guarded B={max(batches)} uid={r.uid}: tokens "
                f"diverge from greedy_generate")

    # crash-safety overhead arm: the same B=GATE_BATCH bf16 engine with
    # the write-ahead journal + async snapshot every SNAPSHOT_EVERY decode
    # steps (docs/DESIGN_robustness.md §6).  Paired min-of-`snapshot_reps`
    # timing vs a durability-off engine gates the <=5% cost; the restore
    # probe times resume_engine until the first post-restore synced token.
    if snapshot_reps:
        off_best, snapped = _snapshot_overhead_arms(
            params, cfg, reqs, batch=max(batches), cache_len=cache_len,
            reps=snapshot_reps)
        tps_off = off_best["count"] / off_best["seconds"]
        tps_snap = snapped["count"] / snapped["seconds"]
        restore_s = _restore_to_first_token(
            params, cfg, reqs, batch=max(batches), cache_len=cache_len)
        rows.append({"arm": "engine_snapshot", "batch": max(batches),
                     "kv_mode": "bf16", "tokens": snapped["count"],
                     "seconds": snapped["seconds"],
                     "tokens_per_s": tps_snap,
                     "speedup_vs_greedy": tps_snap / tps_greedy,
                     "speedup_vs_warm": tps_snap / tps_warm,
                     "snapshot_every": SNAPSHOT_EVERY,
                     "snapshot_overhead": tps_off / tps_snap,
                     "restore_to_first_token_s": restore_s})
        for r in reqs:       # durability must not change a single token
            if not np.array_equal(snapped["tokens"][r.uid],
                                  greedy["tokens"][r.uid]):
                parity_failures.append(
                    f"engine_snapshot B={max(batches)} uid={r.uid}: tokens "
                    f"diverge from greedy_generate")

    # observability overhead arm: the same B=GATE_BATCH bf16 engine with
    # the full repro.obs stack on (dedicated Observer + obs.enable()
    # profiler scope) paired min-of-`obs_reps` against the default
    # engine.  A sanity assert confirms the instrumented run actually
    # recorded one request span per request — an accidentally-dark
    # observer would make the overhead gate vacuous.
    if obs_reps:
        off_best, observed = _obs_overhead_arms(
            params, cfg, reqs, batch=max(batches), cache_len=cache_len,
            reps=obs_reps)
        tps_off = off_best["count"] / off_best["seconds"]
        tps_obs = observed["count"] / observed["seconds"]
        structure = observed["observer"].trace.span_structure()
        n_req_spans = sum(1 for _, name, _ in structure if name == "request")
        rows.append({"arm": "engine_obs", "batch": max(batches),
                     "kv_mode": "bf16", "tokens": observed["count"],
                     "seconds": observed["seconds"],
                     "tokens_per_s": tps_obs,
                     "speedup_vs_greedy": tps_obs / tps_greedy,
                     "speedup_vs_warm": tps_obs / tps_warm,
                     "obs_overhead": tps_off / tps_obs,
                     "request_spans": n_req_spans})
        if n_req_spans < len(reqs):
            parity_failures.append(
                f"engine_obs B={max(batches)}: only {n_req_spans} request "
                f"spans recorded for {len(reqs)} requests")
        for r in reqs:       # instrumentation must not change a token
            if not np.array_equal(observed["tokens"][r.uid],
                                  greedy["tokens"][r.uid]):
                parity_failures.append(
                    f"engine_obs B={max(batches)} uid={r.uid}: tokens "
                    f"diverge from greedy_generate")

    acc = _logprob_accuracy(params, cfg, reqs, cache_len)
    return rows, acc, parity_failures


def main(argv: Optional[Sequence[str]] = None,
         out_json: str = "BENCH_serving.json"):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: 8 requests, batches {2, 8}")
    ap.add_argument("--requests", type=int, default=0,
                    help="override request count")
    ap.add_argument("--max-new", type=int, default=0)
    ap.add_argument("--guard-overhead", action="store_true",
                    help="gate ff.guard(mode='check') probe overhead at "
                         f"B={GATE_BATCH} (<= {GUARD_OVERHEAD_GATE:.2f}x "
                         "tokens/s vs guard='off', min-of-3 paired runs)")
    ap.add_argument("--snapshot-overhead", action="store_true",
                    help="gate the crash-safety cost (WAL + async snapshot "
                         f"every {SNAPSHOT_EVERY} decode steps) at "
                         f"B={GATE_BATCH} (<= {SNAPSHOT_OVERHEAD_GATE:.2f}x "
                         "tokens/s vs durability off, min-of-3 paired "
                         "runs) and record restore_to_first_token_s")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="gate the full repro.obs instrumentation cost at "
                         f"B={GATE_BATCH} (<= {OBS_OVERHEAD_GATE:.2f}x "
                         "tokens/s vs the default engine, min-of-3 paired "
                         "runs)")
    ap.add_argument("--out", type=str, default=out_json)
    args = ap.parse_args([] if argv is None else argv)

    n = args.requests or (8 if args.quick else 16)
    max_new = args.max_new or (16 if args.quick else 24)
    batches = (2, GATE_BATCH) if args.quick else (2, 4, GATE_BATCH)

    rows, acc, parity_failures = run(
        num_requests=n, max_new=max_new, batches=batches,
        guard_reps=3 if args.guard_overhead else 1,
        snapshot_reps=3 if args.snapshot_overhead else 0,
        obs_reps=3 if args.obs_overhead else 0)

    print("serving: arm,batch,kv_mode,tok/s,vs_greedy,vs_warm")
    for r in rows:
        extra = (f",guard_overhead={r['guard_overhead']:.3f}x"
                 if "guard_overhead" in r else "")
        if "snapshot_overhead" in r:
            extra += (f",snapshot_overhead={r['snapshot_overhead']:.3f}x,"
                      f"restore={r['restore_to_first_token_s']:.2f}s")
        if "obs_overhead" in r:
            extra += f",obs_overhead={r['obs_overhead']:.3f}x"
        print(f"{r['arm']},{r['batch']},{r['kv_mode']},"
              f"{r['tokens_per_s']:.1f},{r['speedup_vs_greedy']:.2f}x,"
              f"{r['speedup_vs_warm']:.2f}x{extra}")
    print(f"ff logprob max rel err vs f64: {acc['ff_logprob_max_rel_err']:.3e}"
          f" (= 2^{np.log2(max(acc['ff_logprob_max_rel_err'], 1e-300)):.1f},"
          f" tol 2^-40); f32 tier: {acc['f32_logprob_max_rel_err']:.3e}")

    payload = {
        "bench": "serving",
        "backend": ff.backend(),
        "jax": jax.__version__,
        "config": BENCH_CFG,
        "num_requests": n,
        "max_new": max_new,
        "accuracy": acc,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out} (backend={payload['backend']})")

    failures = list(parity_failures)
    if acc["ff_logprob_max_rel_err"] > LOGPROB_TOL:
        failures.append(
            f"FF token logprob err {acc['ff_logprob_max_rel_err']:.3e} "
            f"exceeds 2^-40")
    gate_rows = [r for r in rows if r["arm"] == "engine"
                 and r["batch"] >= GATE_BATCH and r["kv_mode"] == "bf16"]
    if not gate_rows:
        failures.append(f"no engine row at batch >= {GATE_BATCH} to gate")
    for r in gate_rows:
        if r["speedup_vs_greedy"] < SPEEDUP_GATE:
            failures.append(
                f"engine B={r['batch']} speedup {r['speedup_vs_greedy']:.2f}x"
                f" < {SPEEDUP_GATE}x vs sequential greedy_generate")
    if args.guard_overhead:
        g = next(r for r in rows if r["arm"] == "engine_guarded")
        if g["guard_overhead"] > GUARD_OVERHEAD_GATE:
            failures.append(
                f"guard='check' overhead {g['guard_overhead']:.3f}x at "
                f"B={g['batch']} exceeds {GUARD_OVERHEAD_GATE:.2f}x")
    if args.snapshot_overhead:
        s = next(r for r in rows if r["arm"] == "engine_snapshot")
        if s["snapshot_overhead"] > SNAPSHOT_OVERHEAD_GATE:
            failures.append(
                f"snapshot_every={s['snapshot_every']} overhead "
                f"{s['snapshot_overhead']:.3f}x at B={s['batch']} exceeds "
                f"{SNAPSHOT_OVERHEAD_GATE:.2f}x")
    if args.obs_overhead:
        o = next(r for r in rows if r["arm"] == "engine_obs")
        if o["obs_overhead"] > OBS_OVERHEAD_GATE:
            failures.append(
                f"obs instrumentation overhead {o['obs_overhead']:.3f}x at "
                f"B={o['batch']} exceeds {OBS_OVERHEAD_GATE:.2f}x")
    if failures:
        print("SERVING GATE FAILURES:")
        for f_ in failures:
            print(" ", f_)
        sys.exit(1)
    print(f"serving gates OK (>= {SPEEDUP_GATE}x at B>={GATE_BATCH}, "
          f"logprob <= 2^-40, token parity)")
    return rows


if __name__ == "__main__":
    main(sys.argv[1:])
