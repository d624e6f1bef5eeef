"""Find an open-loop cell's knee: the highest arrival rate at which the
engine's wait queue does not grow across the window.

    python3 bench/tools/sweep.py --workload granite_3_2b.chat \
        --rates 3,4,5,6 --seconds 20 --seed 5

One process runs the cell at each rate (no comparison with the
reference) and prints one JSON line per rate: the end-to-end metrics and
the mean queue depth over the first and the last third of the window.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    base = run.resolve(bench, args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        parts = copy.deepcopy(base)
        parts["mix"]["arrival"]["rate_per_s"] = rate
        try:
            res = run.run_cell(args.workload, args.seed, args.seconds, False,
                               bench=bench, parts=parts, check=False)
        except run.DeviceError as e:
            run.log(f"no result: {e}")
            return 2
        print(json.dumps({"rate_per_s": rate, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
