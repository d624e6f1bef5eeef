"""Weak/strong scaling of the mesh-partitioned FF tier (``repro.ff.sharded``).

Runs on a simulated CPU mesh (``--xla_force_host_platform_device_count``,
default 8 devices) and emits ``BENCH_distributed.json``:

    PYTHONPATH=src python -m benchmarks.table_distributed            # full
    PYTHONPATH=src python -m benchmarks.table_distributed --quick    # CI gate

Methodology — simulated devices share the machine's physical cores, so two
numbers are reported per row and it matters which one you read:

* ``wall_ms``: the whole sharded program timed on the D-device mesh.  On
  an oversubscribed host this CANNOT show real scaling (the single-device
  baseline already multithreads across the same cores; D fake devices add
  scheduling + copy overhead), so expect wall_speedup <= 1 here.  It is
  recorded because it is the honest end-to-end cost on THIS machine and
  gates functional regressions.
* ``critical_ms = local_ms + combine_ms``: the per-device critical path —
  the measured per-shard local program (the inner impl at the (M, K/D, N)
  shard shape, run alone on one device) plus the measured *per-device
  combine compute* (a tree all-reduce costs each device ceil(log2 D)
  plane-adds per limb for ``psum``, resp. log2(D) Add22_accurate folds for
  ``tree`` — that fold chain is timed as a one-device program).  This is
  the wall time a D-device mesh with one shard per physical device would
  see, EXCLUDING interconnect transfer: a simulated mesh has no
  interconnect to measure (its "collectives" are host memcpys contending
  for the same 2 cores — neither a network model nor free), so transfer
  cost is out of scope here and the combine term charges the compute a
  real device provably pays.  ``scaled_speedup = single_ms / critical_ms``
  is the strong-scaling headline.

Why the FF tier scales SUPER-linearly in compute terms: the single-device
fast path at large K is fold-dominated (K/block_k sequential GEMM+Add22
passes over the full (M, N) output — the 3x-naive column in the README
matrix), while a K-split shard needs ONE local GEMM + renormalize and the
compensated combine replaces the serial fold chain entirely.  Sharding
removes work per device faster than 1/D.

Accuracy gates (always on): the sharded fast/accurate-class results on the
mesh must match the f64 oracle within their documented NUMERICS.md bounds
(2^-19 / 2^-44 classes) — a scaling number from a wrong result is void.
"""

import argparse
import json
import os
import sys
import time

_f = os.environ.get("XLA_FLAGS", "")
if "--xla_cpu_max_isa" not in _f:
    _f = ("--xla_cpu_max_isa=SSE4_2 " + _f).strip()
if "--xla_force_host_platform_device_count" not in _f:
    _f = (_f + " --xla_force_host_platform_device_count=8").strip()
os.environ["XLA_FLAGS"] = _f

import numpy as np                                     # noqa: E402
import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P      # noqa: E402

import repro.ff as ff                                  # noqa: E402
from repro.ff import sharded as ffsh                   # noqa: E402
from repro.ff import tuning                            # noqa: E402
from repro.core.ff import FF                           # noqa: E402

FAST_BOUND = 2.0 ** -19        # fast class ceiling (docs/NUMERICS.md)
ACC_BOUND = 2.0 ** -44         # accurate class ceiling


def _mesh(d: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:d]), ("x",))


def _mesh_call(mesh, fn):
    """jit ``fn`` and enter the on_mesh scope around every call, so the
    trace (first call, inside the timing harness's warmup) sees it."""
    jfn = jax.jit(fn)

    def call(*a):
        with ff.on_mesh(mesh, axis="x"):
            return jfn(*a)
    return call


def _time(fns, args, rounds: int) -> list:
    """Shared shuffled-interleave protocol (min-of-rounds seconds/call)."""
    res = tuning.time_interleaved(fns, args, reps=1, rounds=rounds,
                                  sample_target_s=0.02, min_reps=1)
    return [r[0] if r is not None else None for r in res]


def _combine_local_probe(d, M, N, how):
    """Per-device combine COMPUTE as a one-device program (see module
    docstring): ``ceil(log2 d)`` incoming (M, N) FF partials folded into
    the local one — plane adds + a final TwoSum renormalize for ``psum``
    (what a tree all-reduce costs each device), Add22_accurate folds for
    ``tree`` (exactly the butterfly's per-device work)."""
    from repro.core import ff as core_ff
    from repro.core import transforms as T

    steps = max(int(np.ceil(np.log2(d))), 0) if d > 1 else 0
    rng = np.random.default_rng(7)
    hi = jnp.asarray(rng.standard_normal((steps + 1, M, N))
                     .astype(np.float32))
    lo = jnp.asarray((np.asarray(hi) * 1e-8).astype(np.float32))

    def body(h, l):
        if how == "psum":
            hh, ll = h[0], l[0]
            for s in range(1, steps + 1):
                hh = hh + h[s]
                ll = ll + l[s]
            s2, e = T.two_sum(hh, ll)
            return s2, e
        r = FF(h[0], l[0])
        for s in range(1, steps + 1):
            r = core_ff.add22_accurate(r, FF(h[s], l[s]))
        return r.hi, r.lo

    return jax.jit(body), (hi, lo)


def _err(R, E, S) -> float:
    return float((np.abs(np.asarray(R.to_f64()) - E) / S).max())


def bench_matmul(mode: str, M: int, K_of, N: int, devices, rounds: int,
                 oracle_at) -> list:
    """One scaling sweep.  ``K_of(d)`` gives the global K per device count
    (constant for strong scaling, 512*d-style for weak)."""
    rng = np.random.default_rng(0)
    rows = []
    for klass, acc in (("fast", False), ("accurate", True)):
        impl = "sharded_accurate" if acc else "sharded"
        # single-device baseline at each K (strong: one K; weak: per-d)
        singles = {}
        for d in devices:
            K = K_of(d)
            if K in singles:
                continue
            A = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
            B = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))
            sfn = jax.jit(lambda a, b: ff.matmul(
                a, b, impl="tuned_accurate" if acc else None).astuple())
            (t1,) = _time([sfn], (A, B), rounds)
            singles[K] = (A, B, t1)
        for d in devices:
            K = K_of(d)
            A, B, t_single = singles[K]
            kl = K // d
            mesh = _mesh(d)
            how = "tree" if acc else "psum"
            inner = ffsh._resolve_inner("matmul", None, acc, (M, kl, N))
            wall = _mesh_call(mesh, lambda a, b, impl=impl: ff.matmul(
                a, b, impl=impl).astuple())
            local = jax.jit(lambda a, b, inner=inner: ff.matmul(
                a, b, impl=inner).astuple())
            cfn, cargs = _combine_local_probe(d, M, N, how)
            t_wall, = _time([wall], (A, B), rounds)
            t_local, = _time([local], (A[:, :kl], B[:kl]), rounds)
            t_comb, = _time([cfn], cargs, rounds)
            row = {
                "mode": mode, "op": f"matmul_{klass}", "M": M, "K": K,
                "N": N, "devices": d, "inner": inner, "combine": how,
                "single_ms": t_single * 1e3, "wall_ms": t_wall * 1e3,
                "local_ms": t_local * 1e3, "combine_ms": t_comb * 1e3,
                "critical_ms": (t_local + t_comb) * 1e3,
                "wall_speedup": t_single / t_wall,
                "scaled_speedup": t_single / (t_local + t_comb),
            }
            if (mode, d) in oracle_at:
                E = np.asarray(A, np.float64) @ np.asarray(B, np.float64)
                S = (np.abs(np.asarray(A, np.float64))
                     @ np.abs(np.asarray(B, np.float64)))
                with ff.on_mesh(mesh, axis="x"):
                    R = jax.jit(lambda a, b: ff.matmul(a, b, impl=impl))(A, B)
                e = _err(R, E, S)
                row["err_vs_oracle"] = e
                bound = ACC_BOUND if acc else FAST_BOUND
                assert e < bound, (
                    f"{klass} sharded matmul {M}x{K}x{N} on {d} devices: "
                    f"err {e:.3e} exceeds the documented {bound:.3e} bound")
            rows.append(row)
            print(f"  {row['op']:16s} {mode:6s} K={K:5d} d={d}  "
                  f"single {row['single_ms']:8.1f}ms  wall "
                  f"{row['wall_ms']:8.1f}ms  critical "
                  f"{row['critical_ms']:8.1f}ms  scaled x"
                  f"{row['scaled_speedup']:.2f}"
                  + (f"  err 2^{np.log2(row['err_vs_oracle']):.1f}"
                     if "err_vs_oracle" in row else ""))
    return rows


def bench_sum(n: int, devices, rounds: int) -> list:
    rng = np.random.default_rng(2)
    v = (rng.standard_normal(n) * 10.0 ** rng.uniform(-4, 4, n)
         ).astype(np.float32)
    x = jnp.asarray(v)
    exact = float(np.sum(v.astype(np.float64)))
    sfn = jax.jit(lambda u: ff.sum(u).astuple())
    (t1,) = _time([sfn], (x,), rounds)
    rows = []
    for d in devices:
        mesh = _mesh(d)
        wall = _mesh_call(mesh, lambda u: ff.sum(u).astuple())
        local = jax.jit(lambda u: ff.sum(u, impl="blocked").astuple())
        t_wall, = _time([wall], (x,), rounds)
        t_local, = _time([local], (x[: n // d],), rounds)
        with ff.on_mesh(mesh, axis="x"):
            s = jax.jit(lambda u: ff.sum(u))(x)
        rel = abs(float(s.to_f64()) - exact) / abs(exact)
        assert rel < 2.0 ** -40, (
            f"sharded ff.sum on {d} devices: rel err {rel:.3e} exceeds the "
            f"documented compensated bound")
        rows.append({
            "mode": "strong", "op": "sum", "n": n, "devices": d,
            "combine": "tree", "single_ms": t1 * 1e3,
            "wall_ms": t_wall * 1e3, "local_ms": t_local * 1e3,
            "combine_ms": None, "critical_ms": t_local * 1e3,
            "wall_speedup": t1 / t_wall,
            "scaled_speedup": t1 / t_local, "rel_err": rel,
        })
        print(f"  sum              strong n={n} d={d}  single {t1*1e3:8.1f}ms"
              f"  wall {t_wall*1e3:8.1f}ms  local {t_local*1e3:8.1f}ms  "
              f"scaled x{t1 / t_local:.2f}  rel {rel:.1e}")
    return rows


def check_regression(rows, baseline_path: str) -> int:
    """Ratio-based gate against a committed baseline: a row's
    scaled_speedup collapsing below baseline/1.3 fails (absolute times are
    machine-local; speedup ratios are portable)."""
    with open(baseline_path) as f:
        base = json.load(f)

    def key(r):
        return (r["mode"], r["op"], r.get("K"), r.get("n"), r["devices"])

    old = {key(r): r for r in base["rows"]}
    failures = overlap = 0
    for r in rows:
        b = old.get(key(r))
        if b is None:
            continue
        overlap += 1
        if r["scaled_speedup"] < b["scaled_speedup"] / 1.3:
            print(f"[gate] REGRESSION {key(r)}: scaled_speedup "
                  f"{r['scaled_speedup']:.2f} < baseline "
                  f"{b['scaled_speedup']:.2f}/1.3", file=sys.stderr)
            failures += 1
    if overlap == 0:
        print("[gate] FAIL: zero overlapping rows with the baseline — "
              "shape/device mismatch, the gate checked nothing",
              file=sys.stderr)
        return 1
    print(f"[gate] {overlap} rows checked vs {baseline_path}, "
          f"{failures} regressions")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser(
        description="FF mesh scaling bench (see module docstring)")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: 1024-class shapes, fewer rounds")
    ap.add_argument("--devices", default=None,
                    help="comma list of device counts (default 1,2,4,8)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default="BENCH_distributed.json")
    ap.add_argument("--check-regression", metavar="BASELINE")
    args = ap.parse_args()

    ndev = len(jax.devices())
    devices = ([int(x) for x in args.devices.split(",")] if args.devices
               else [d for d in (1, 2, 4, 8) if d <= ndev])
    rounds = args.rounds or (2 if args.quick else 3)
    if args.quick:
        M = N = 1024
        K_strong = 1024
        k_weak = 256
        n_sum = 1 << 20
    else:
        M = N = 4096
        K_strong = 4096
        k_weak = 512
        n_sum = 1 << 22
    dmax = max(devices)
    print(f"[distributed] backend={jax.default_backend()} devices={ndev} "
          f"(simulated; {os.cpu_count()} physical cpus) "
          f"scaling over {devices}")
    print(f"[distributed] strong scaling: matmul {M}x{K_strong}x{N}")
    rows = bench_matmul("strong", M, lambda d: K_strong, N, devices, rounds,
                        oracle_at={("strong", 1), ("strong", dmax)})
    print(f"[distributed] weak scaling: matmul {M}x({k_weak}*D)x{N}")
    rows += bench_matmul("weak", M, lambda d: k_weak * d, N, devices, rounds,
                         oracle_at={("weak", dmax)})
    print(f"[distributed] strong scaling: sum n={n_sum}")
    rows += bench_sum(n_sum, devices, rounds)

    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "devices_simulated": ndev,
            "physical_cpus": os.cpu_count(),
            "quick": bool(args.quick),
            "note": ("wall_ms is oversubscribed (simulated devices share "
                     "physical cores); critical_ms = measured per-shard "
                     "local program + measured combine = per-device wall "
                     "time on a real mesh"),
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    head = [r for r in rows
            if r["mode"] == "strong" and r["devices"] == dmax
            and r["op"].startswith("matmul")]
    for r in head:
        print(f"[distributed] headline: {r['op']} {M}x{K_strong}x{N} on "
              f"{dmax} devices: scaled strong-scaling x"
              f"{r['scaled_speedup']:.2f} (wall x{r['wall_speedup']:.2f} "
              f"oversubscribed)")
    print(f"[distributed] wrote {args.out} ({len(rows)} rows)")
    if args.check_regression:
        sys.exit(check_regression(rows, args.check_regression))


if __name__ == "__main__":
    main()
