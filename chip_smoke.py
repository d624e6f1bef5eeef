#!/usr/bin/env python3
"""Chip smoke: serve Granite-3.0-2B at full width on one TPU, end to end.

    python chip_smoke.py              # one chip: the serving engine + FF ops
    python chip_smoke.py --chips 4    # four chips: the ff.on_mesh tier only

The default run drives the path of ``python -m repro.launch.serve --arch
granite_3_2b --engine`` (its helpers, not a copy of them) at the published
width: 40 layers, d_model 2048, GQA 32/8, d_ff 8192, vocab 49155, f32
weights from ``--seed`` (~10.1 GB), a bf16 paged KV cache.  It serves a
warm-up set and then the measured requests, under the default policy with
the guard probe on, and again under ``ff.policy("ff_reduce")`` so the
compensated-reduction kernels run inside the model.  Checks, each printed:

* every request finishes ``OK``;
* each request's first-token FF logprob is within 2^-40 (relative) of a
  host f64 log-softmax of its prefill logits;
* ``ff.add/mul/div/sqrt`` (XLA and Pallas impls) and
  ``ff.matmul(impl="ozaki")`` at (8, 2048) x (2048, 8192) meet their
  ``docs/NUMERICS.md`` bounds against the host f64 oracle;
* the whole-row Pallas kernels (``ff.softmax``/``ff.logsumexp`` in both
  classes at (8, 16384), ``ff.norm_stats`` and ``ff.mean_sq`` at
  (2048, 2048)) meet theirs;
* ``check_eft_safe()`` holds on the device.

Earlier lines give the device, compile against steady time per phase, peak
device memory and the implementation every FF op resolved to.  The last
line is one JSON object naming the device.  The script exits non-zero,
without that line, when JAX finds no TPU, when any kernel falls back to its
jnp formulation (``FFFallbackWarning`` is an error here) or when any check
fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
import warnings

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "granite_3_2b"
LOGPROB_TOL = 2.0 ** -40
# docs/NUMERICS.md: relative bounds (add: same-sign operands, so the sloppy
# Add22 bound is relative too; matmul: vs |A|.|B|)
OP_BOUNDS = {"add": 2.0 ** -44, "mul": 2.0 ** -44, "div": 2.0 ** -43,
             "sqrt": 2.0 ** -44}
MATMUL_ACC_BOUND = 2.0 ** -44
MATMUL_FAST_BOUND = 2.0 ** -19


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    log(f"{'PASS' if ok else 'FAIL'} {what}")
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)`` with every output ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def first_and_steady(name: str, fn, *args):
    """Run ``fn`` twice: the first call includes compilation."""
    out, t_first = timed(fn, *args)
    out, t_steady = timed(fn, *args)
    log(f"time {name}: first call (compile+run) {t_first:.3f}s, "
        f"steady {t_steady * 1e3:.3f}ms")
    return out


def ff_f64(r) -> np.ndarray:
    return np.asarray(r.hi, np.float64) + np.asarray(r.lo, np.float64)


# ---------------------------------------------------------------------------
# one chip: FF ops against the host f64 oracle
# ---------------------------------------------------------------------------

def phase_ff_ops(rng) -> None:
    import jax
    import jax.numpy as jnp
    import repro.ff as ff
    from repro.core.selfcheck import check_eft_safe

    check(check_eft_safe(), "check_eft_safe() on the device")
    shape = (1024, 1024)
    a64 = rng.uniform(0.5, 2.0, shape) * np.exp2(rng.integers(-8, 8, shape))
    b64 = rng.uniform(0.5, 2.0, shape) * np.exp2(rng.integers(-8, 8, shape))
    a, b = ff.from_f64(a64), ff.from_f64(b64)
    a64, b64 = ff_f64(a), ff_f64(b)      # the FF inputs, exactly
    exact = {"add": a64 + b64, "mul": a64 * b64, "div": a64 / b64,
             "sqrt": np.sqrt(a64)}
    for op, bound in OP_BOUNDS.items():
        for impl in ("jnp", "pallas"):
            fn = getattr(ff, op)
            if op == "sqrt":
                f = jax.jit(lambda x, fn=fn, impl=impl: fn(x, impl=impl))
                r = first_and_steady(f"ff.{op}[{impl}] {shape}", f, a)
            else:
                f = jax.jit(lambda x, y, fn=fn, impl=impl:
                            fn(x, y, impl=impl))
                r = first_and_steady(f"ff.{op}[{impl}] {shape}", f, a, b)
            err = float(np.max(np.abs(ff_f64(r) - exact[op])
                               / np.abs(exact[op])))
            check(err <= bound, f"ff.{op}[{impl}] max rel err "
                  f"2^{np.log2(max(err, 2.0 ** -80)):.1f} <= "
                  f"2^{np.log2(bound):.0f}")

    A = rng.standard_normal((8, 2048)).astype(np.float32)
    B = rng.standard_normal((2048, 8192)).astype(np.float32)
    E = A.astype(np.float64) @ B.astype(np.float64)
    S = np.abs(A.astype(np.float64)) @ np.abs(B.astype(np.float64))
    f = jax.jit(lambda x, y: ff.matmul(x, y, impl="ozaki"))
    r = first_and_steady("ff.matmul[ozaki] (8,2048)x(2048,8192)", f,
                         jnp.asarray(A), jnp.asarray(B))
    err = float(np.max(np.abs(ff_f64(r) - E) / S))
    check(err <= MATMUL_ACC_BOUND,
          f"ff.matmul[ozaki] err vs |A||B| 2^{np.log2(err):.1f} <= 2^-44")


def ulps(got, want64) -> float:
    """Max distance of ``got`` from ``want64`` in f32 ulps of ``want64``."""
    want32 = np.abs(want64).astype(np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want64)
                        / np.spacing(want32).astype(np.float64)))


def check_norm_stats(tag: str, mu, var, X: np.ndarray) -> None:
    X64 = X.astype(np.float64)
    mu64, var64 = X64.mean(-1), X64.var(-1)
    em = float(np.max(np.abs(np.asarray(mu, np.float64) - mu64)
                      / np.abs(X64).mean(-1)))
    ev = float(np.max(np.abs(np.asarray(var, np.float64) - var64) / var64))
    check(em <= 2.0 ** -22 and ev <= 2.0 ** -22,
          f"{tag} f32 outputs vs f64: mean 2^{np.log2(max(em, 2.0 ** -80)):.1f}, "
          f"var 2^{np.log2(max(ev, 2.0 ** -80)):.1f} <= 2^-22")


def phase_row_kernels(rng) -> None:
    """The whole-row Pallas kernels, compiled for the chip, against the
    host f64 oracle (bounds of docs/NUMERICS.md and tests/test_fusion.py):
    softmax/logsumexp in both classes at the largest row they take, and
    the RMSNorm/LayerNorm statistics at a model's width."""
    import jax
    import jax.numpy as jnp
    import repro.ff as ff
    from repro.kernels.ff_fused import MAX_FUSED_COLS

    shape = (8, MAX_FUSED_COLS)
    x = rng.standard_normal(shape) * 4
    m = x.max(-1, keepdims=True)
    # centre each row on lse 0.5, so its f32 ulp is small and the classes
    # differ measurably (as in tests/test_ff_math.py)
    x = (x - (m + np.log(np.exp(x - m).sum(-1, keepdims=True))) + 0.5)
    x = x.astype(np.float32)
    x64 = x.astype(np.float64)
    m = x64.max(-1, keepdims=True)
    e = np.exp(x64 - m)
    lse64 = (m + np.log(e.sum(-1, keepdims=True)))[:, 0]
    sm64 = e / e.sum(-1, keepdims=True)
    xd = jnp.asarray(x)
    for op, want in (("logsumexp", lse64), ("softmax", sm64)):
        fn = getattr(ff, op)
        ref = jax.jit(lambda v, fn=fn: fn(v, impl="jnp"))(xd)
        f = jax.jit(lambda v, fn=fn: fn(v, impl="pallas"))
        got = first_and_steady(f"ff.{op}[pallas] {shape}", f, xd)
        # the fast class takes the device's f32 exp and log as they are:
        # its contract is agreement with the jnp impl on the same device
        u = ulps(got, np.asarray(ref, np.float64))
        check(u <= 2, f"ff.{op}[pallas] (fast class) {u:.2f} <= 2 ulp from "
              f"the jnp impl; vs f64 {ulps(got, want):.2f} ulp (jnp impl "
              f"{ulps(ref, want):.2f}), max abs err "
              f"{float(np.max(np.abs(np.asarray(got, np.float64) - want))):.3g}")
        f = jax.jit(lambda v, fn=fn: fn(v, impl="ff"))
        got = first_and_steady(f"ff.{op}[ff] {shape}", f, xd)
        u, bound = ulps(got, want), 0.6 if op == "logsumexp" else 1.0
        check(u <= bound, f"ff.{op}[ff] (accurate class, fused kernel) "
              f"{u:.3f} ulp vs f64 <= {bound}")

    X = (rng.standard_normal((2048, 2048)) * 3 + 1).astype(np.float32)
    Xd = jnp.asarray(X)
    mu, var = first_and_steady("ff.norm_stats[pallas] (2048,2048)", jax.jit(
        lambda v: ff.norm_stats(v, impl="pallas")), Xd)
    mu_j, var_j = jax.jit(lambda v: ff.norm_stats(v, impl="jnp"))(Xd)
    um = ulps(mu, np.asarray(mu_j, np.float64))
    uv = ulps(var, np.asarray(var_j, np.float64))
    check(um <= 1 and uv <= 2, f"ff.norm_stats[pallas] vs the jnp impl: "
          f"mean {um:.0f} <= 1 ulp, var {uv:.0f} <= 2 ulp")
    check_norm_stats("ff.norm_stats[pallas]", mu, var, X)
    ms = first_and_steady("ff.mean_sq[fused] (2048,2048)", jax.jit(
        lambda v: ff.mean_sq(v, impl="fused")), Xd)
    u = ulps(ms, (X.astype(np.float64) ** 2).mean(-1))
    check(u <= 1, f"ff.mean_sq[fused] {u:.3f} ulp vs f64 <= 1")


# ---------------------------------------------------------------------------
# one chip: the serving engine at full width
# ---------------------------------------------------------------------------

def check_logprobs(eng, requests, results, tag: str) -> None:
    """First-token FF logprob vs a host f64 log-softmax of the request's
    prefill logits (re-run through the engine's own prefill program)."""
    worst = 0.0
    for req in requests:
        res = results[req.uid]
        logits, _ = eng.prefill(req.prompt)
        x = np.asarray(logits[0], np.float64)
        tok = int(res.tokens[0])
        check(tok == int(np.argmax(x)),
              f"{tag} uid {req.uid}: first token is the prefill argmax")
        # (x_tok - m) - log1p(rest): exact for a near-certain row too
        e = np.exp(x - x.max())
        e[tok] = 0.0
        ref = -np.log1p(np.sum(e))
        got = float(np.float64(res.logprobs_ff[0, 0])
                    + np.float64(res.logprobs_ff[0, 1]))
        worst = max(worst, abs(got - ref) / abs(ref))
    check(worst <= LOGPROB_TOL,
          f"{tag}: first-token FF logprob max rel err vs f64 "
          f"2^{np.log2(max(worst, 2.0 ** -80)):.1f} <= 2^-40 "
          f"({len(requests)} requests)")


def serve_pass(params, cfg, tag: str, *, n_req: int, lens, max_new: int,
               rng, guard: str) -> None:
    from repro.launch.serve import make_requests, serve
    from repro.serve import OK, ServeEngine

    eng = ServeEngine(params, cfg, max_batch=8, max_ctx=512, kv_mode="bf16",
                      guard=guard)
    warm = [dataclasses.replace(r, uid=10_000 + r.uid)
            for r in make_requests(cfg, sorted(set(lens)), 2, rng)]
    _, t_warm = serve(eng, warm)
    log(f"time {tag} warm-up ({len(warm)} requests, one per prompt length "
        f"{sorted(set(lens))}; compiles prefill per length + decode + "
        f"scoring): {t_warm:.1f}s")
    reqs = make_requests(cfg, [int(lens[i % len(lens)]) for i in
                               range(n_req)], max_new, rng)
    results, dt = serve(eng, reqs)
    n_tok = sum(len(r.tokens) for r in results.values())
    statuses = sorted({r.status for r in results.values()})
    log(f"time {tag} steady: {len(reqs)} requests, {n_tok} tokens in "
        f"{dt:.2f}s ({n_tok / dt:.1f} tok/s, host clock)")
    check(all(r.status == OK for r in results.values())
          and len(results) == len(reqs),
          f"{tag}: all {len(reqs)} requests OK (statuses {statuses})")
    check(all(len(r.tokens) == max_new for r in results.values()),
          f"{tag}: every request emitted max_new={max_new} tokens")
    check(all(np.isfinite(r.logprobs).all() for r in results.values()),
          f"{tag}: f32 logprobs finite")
    check_logprobs(eng, reqs, results, tag)


def phase_serve(args, rng) -> None:
    import jax
    import repro.ff as ff
    from repro.configs import get_config
    from repro.launch.serve import build_params

    cfg = get_config(ARCH)
    log(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size} (published width, random weights, "
        f"seed {args.seed})")
    params, t = timed(build_params, cfg, args.seed)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"time build_params (jit, compile+run): {t:.1f}s; {n / 1e9:.3f}B "
        f"params, {nbytes / 1e9:.2f} GB f32")
    lens = [int(x) for x in rng.choice(np.arange(32, 257, 16), size=2,
                                       replace=False)]
    serve_pass(params, cfg, "serve[ff_master, guard=check]", n_req=8,
               lens=lens, max_new=16, rng=rng, guard="check")
    with ff.policy("ff_reduce"):
        serve_pass(params, cfg, "serve[ff_reduce]", n_req=4, lens=lens,
                   max_new=8, rng=rng, guard="off")


def report_resolutions() -> None:
    from repro import obs
    pat = re.compile(r'ff_dispatch_resolutions_total\{backend="([^"]*)",'
                     r'impl="([^"]*)",op="([^"]*)",shape="([^"]*)",'
                     r'source="([^"]*)"\}')
    rows = set()
    for series in obs.REGISTRY.snapshot()["counters"]:
        m = pat.fullmatch(series)
        if m:
            backend, impl, op, shape, source = m.groups()
            rows.add((op, shape, impl, source, backend))
    for op, shape, impl, source, backend in sorted(rows):
        log(f"resolved ff.{op}[{shape or '-'}] -> {impl} "
            f"({source}, backend {backend})")
    check(all(r[4] == "tpu" for r in rows),
          f"every FF resolution ({len(rows)}) was made for backend tpu")


# ---------------------------------------------------------------------------
# four chips: the ff.on_mesh tier
# ---------------------------------------------------------------------------

def phase_mesh(rng) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import repro.ff as ff
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    check(len(devs) >= 4, f"four devices visible (found {len(devs)})")
    mesh = make_mesh((4,), ("x",), devices=devs[:4])
    log(f"mesh {dict(mesh.shape)} over {[d.id for d in devs[:4]]}")

    def on_four(x, spec):
        y = jax.device_put(x, NamedSharding(mesh, spec))
        check(len(y.sharding.device_set) == 4
              and len({s.device for s in y.addressable_shards}) == 4,
              f"operand {x.shape} {spec} lands on 4 devices")
        return y

    A = rng.standard_normal((8, 8192)).astype(np.float32)
    B = rng.standard_normal((8192, 2048)).astype(np.float32)
    E = A.astype(np.float64) @ B.astype(np.float64)
    S = np.abs(A.astype(np.float64)) @ np.abs(B.astype(np.float64))
    Ad, Bd = on_four(A, P(None, "x")), on_four(B, P("x", None))
    for impl, single, bound in (("sharded", "hybrid", MATMUL_FAST_BOUND),
                                ("sharded_accurate", "ozaki",
                                 MATMUL_ACC_BOUND)):
        with ff.on_mesh(mesh, axis="x"):
            f = jax.jit(lambda x, y, impl=impl: ff.matmul(x, y, impl=impl))
            hlo = f.lower(Ad, Bd).compile().as_text()
            r = first_and_steady(f"ff.matmul[{impl}] (8,8192)x(8192,2048) "
                                 f"K split 4 ways", f, Ad, Bd)
        g = jax.jit(lambda x, y, single=single: ff.matmul(x, y, impl=single))
        r1 = first_and_steady(f"ff.matmul[{single}] one device", g,
                              jnp.asarray(A), jnp.asarray(B))
        coll = "all-reduce" if impl == "sharded" else "collective-permute"
        check(coll in hlo, f"ff.matmul[{impl}] program holds {coll}")
        err = float(np.max(np.abs(ff_f64(r) - E) / S))
        err1 = float(np.max(np.abs(ff_f64(r1) - E) / S))
        check(err <= bound and err1 <= bound,
              f"ff.matmul[{impl}] err 2^{np.log2(err):.1f}, single-device "
              f"[{single}] 2^{np.log2(err1):.1f}, both <= "
              f"2^{np.log2(bound):.0f} vs |A||B|")

    v = (rng.standard_normal(1 << 20)
         * 10.0 ** rng.uniform(-5, 5, 1 << 20)).astype(np.float32)
    exact = float(np.sum(v.astype(np.float64)))
    mag = float(np.sum(np.abs(v.astype(np.float64))))
    vd = on_four(v, P("x"))
    with ff.on_mesh(mesh, axis="x"):
        f = jax.jit(lambda x: ff.sum(x))
        s = first_and_steady("ff.sum[sharded] 2^20, tree combine", f, vd)
    s1 = first_and_steady("ff.sum[blocked] one device",
                          jax.jit(lambda x: ff.sum(x)), jnp.asarray(v))
    e, e1 = (abs(float(ff_f64(x)) - exact) / mag for x in (s, s1))
    check(e <= 4 * 2.0 ** -44 and e1 <= 4 * 2.0 ** -44,
          f"ff.sum sharded err 2^{np.log2(max(e, 2.0 ** -80)):.1f}, "
          f"single 2^{np.log2(max(e1, 2.0 ** -80)):.1f} (vs sum|x|) "
          f"<= 2^-42")

    X = (rng.standard_normal((2048, 2048)) * 3 + 1).astype(np.float32)
    Xd = on_four(X, P("x", None))
    with ff.on_mesh(mesh, axis="x"):
        f = jax.jit(lambda x: ff.norm_stats(x))
        mu, var = first_and_steady("ff.norm_stats[sharded] (2048,2048)", f,
                                   Xd)
    mu1, var1 = first_and_steady("ff.norm_stats[pallas] one device",
                                 jax.jit(lambda x: ff.norm_stats(x,
                                                                 impl="pallas")),
                                 jnp.asarray(X))
    check(np.array_equal(np.asarray(mu), np.asarray(mu1))
          and np.array_equal(np.asarray(var), np.asarray(var1)),
          "ff.norm_stats sharded bitwise equal to the single-device kernel")
    check_norm_stats("ff.norm_stats[sharded]", mu, var, X)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serving engine + FF ops on one chip (default); "
                         "4: only the ff.on_mesh tier on a 4-device mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    from repro.ff import FFFallbackWarning
    from repro.launch.compile_cache import enable_compile_cache

    warnings.simplefilter("error", FFFallbackWarning)
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(rng)
    else:
        phase_ff_ops(rng)
        phase_row_kernels(rng)
        phase_serve(args, rng)
    report_resolutions()
    for d in devs[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"peak HBM device {d.id}: "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB of "
            f"{stats.get('bytes_limit', 0) / 1e9:.3f} GB")
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
