"""Record the small device trace that ``bench/tests`` checks the reduction on.

Serves a few requests through ``repro.serve.ServeEngine`` at a tiny
Granite-shaped size on one TPU, with ``obs.enable()`` on and
``jax.profiler`` tracing, and copies the ``.xplane.pb`` to
``bench/tests/data/engine_trace.xplane.pb`` (or ``--out``).  It also
prints every plane and line of the trace, so the structure can be read by
hand before code is written against it.

    python bench/tools/record_trace_sample.py [--out PATH]
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "bench"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        ROOT, "bench", "tests", "data", "engine_trace.xplane.pb"))
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    import run
    from repro import obs
    from repro.configs import get_config
    from repro.launch.serve import build_params
    from repro.serve import Request, ServeEngine

    cfg = get_config("granite_3_2b").reduced()
    params = build_params(cfg, 0)
    eng = ServeEngine(params, cfg, max_batch=4, max_ctx=128, page_size=16,
                      kv_mode="bf16", guard="off", obs=obs.Observer())
    rng = np.random.default_rng(0)

    def reqs(base):
        return [Request(uid=base + i, prompt=rng.integers(
            1, cfg.vocab_size, size=n).astype(np.int32), max_new=8)
            for i, n in enumerate((16, 32, 16, 32, 16))]

    with obs.enable():
        for r in reqs(0):              # warm: compile every shape first
            eng.submit(r)
        while eng.step():
            pass
        tmp = tempfile.mkdtemp()
        jax.profiler.start_trace(tmp, profiler_options=run.profile_options())
        for r in reqs(100):
            eng.submit(r)
        while True:
            with jax.profiler.TraceAnnotation("bench.step"):
                more = eng.step()
            if not more:
                break
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    shutil.copyfile(path, args.out)
    print(f"trace {os.path.getsize(path)} bytes -> {args.out}")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(args.out)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            print(f"  LINE {line.name!r} n={len(evs)} names={names[:25]}")
            for e in evs[:2]:
                print("    ", e.name, e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:12]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
