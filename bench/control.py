"""Readings for a cell's correctness limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

For each seed, in one process: one run of the cell exactly as
``bench/run.py`` makes it (set-up, window, sample of finished requests),
then the comparison of the program's served tokens and logprobs with the
float32 reference, and of the control with the same reference; the
control is judged against the cell's limits like the program and has to
come out not correct.  The
control is the reference itself computed with float8 (e4m3) inputs to
every matrix product, the precision below the bfloat16 compute the
configurations state; at each position of the same prompts and served
tokens it reads the gap of the token it ranks first.  The benchmark's own
runs never run the control.  Prints one JSON line per seed and a summary:
the largest program reading (the lower end of each limit) and the
smallest control reading (the upper end), of every number the harness
reads, also one the cell's limits leave out.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    lower = {k: 0.0 for k in run.CHECKED}
    upper = {k: float("inf") for k in run.CHECKED}
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               control=True)
        except run.DeviceError as e:
            run.log(f"no result: {e}")
            return 2
        prog = res["control"]["program"]
        for k in lower:
            lower[k] = max(lower[k], prog[k])
        ctl = res["control"]["readings"]
        for k in upper:
            upper[k] = min(upper[k], ctl[k])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control"]["correct"],
                          "program": prog, "control": ctl,
                          "metrics": res["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "control_min": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
