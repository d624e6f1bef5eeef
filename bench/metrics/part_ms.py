"""part_ms.<part>.<cell>: device time per execution of the decode program
of the leaf operations its own map puts in ``<part>``, in ms.

The engine publishes the map under ``obs.enable()``: a ``program``
metadata record of the decode program's instruction names and their
parts (``repro.obs.parts``).  A device operation of the trace is named by
its HLO instruction; it counts when it starts inside an execution of the
decode program (``trace_reduce.decode_modules``) on the same device, and
when no other such operation lies inside it (a ``while`` encloses the
operations of its body, whose times are counted instead).  An operation
the map does not hold is ``other``.

The first read of a run logs every part's ms per step, the share of the
decode programs' device time the named parts cover, and the largest
operations in ``other``.
"""

import bisect
import re
import sys

import trace_reduce

_HLO = re.compile(r"^%?([^\s=]+)")


def _leaves(ops):
    """The operations (sorted by start) that enclose no other one."""
    ops = sorted(ops, key=lambda o: (o["start"], -o["end"]))
    out = []
    for i, o in enumerate(ops):
        j = i + 1
        while j < len(ops) and ops[j]["start"] < o["end"]:
            if ops[j]["end"] <= o["end"]:
                break
            j += 1
        else:
            out.append(o)
    return out


def parts_per_step(run):
    """``{"parts": {part: ms per step}, "cover": share, "other": [(name,
    ms per step)], "steps": n}`` for the run, or None; computed once."""
    if "part_ms" in run:
        return run["part_ms"]
    run["part_ms"] = None
    red = run["trace"]
    mods = trace_reduce.decode_modules(red) if red else []
    if not mods:
        return None
    prog = [e["args"] for e in run["engine_events"]
            if e.get("ph") == "M" and e["name"] == "program"
            and e["args"]["name"] == mods[0]["name"].split("(")[0]]
    if not prog:
        return None
    parts = prog[-1]["parts"]
    by_dev = {}
    for o in red["ops"]:
        by_dev.setdefault(o["device"], []).append(o)
    starts = {d: [o["start"] for o in ops] for d, ops in by_dev.items()}
    total, dev_s, other = {}, 0.0, {}
    for m in mods:
        ops, st = by_dev.get(m["device"], []), starts.get(m["device"], [])
        inside = ops[bisect.bisect_left(st, m["start"]):
                     bisect.bisect_left(st, m["end"])]
        dev_s += m["end"] - m["start"]
        for o in _leaves(inside):
            name = _HLO.match(o["name"]).group(1)
            part = parts.get(name, "other")
            total[part] = total.get(part, 0.0) + o["end"] - o["start"]
            if part == "other":
                other[name] = other.get(name, 0.0) + o["end"] - o["start"]
    n = len(mods)
    named = sum(v for k, v in total.items() if k != "other")
    res = {"parts": {k: 1e3 * v / n for k, v in total.items()},
           "cover": named / dev_s if dev_s else 0.0, "steps": n,
           "other": [(k, 1e3 * v / n) for k, v in
                     sorted(other.items(), key=lambda kv: -kv[1])[:8]]}
    run["part_ms"] = res
    log = [f"parts of {n} decode steps, ms per step: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(res["parts"].items(),
                                         key=lambda kv: -kv[1])),
           f"named parts cover {100 * res['cover']:.2f}% of decode device "
           f"time ({1e3 * dev_s / n:.4f} ms per step)",
           "largest in other, ms per step: " + ", ".join(
               f"{k} {v:.4f}" for k, v in res["other"])]
    for ln in log:
        print(f"[bench] {ln}", file=sys.stderr, flush=True)
    return res


def read(run, name):
    res = parts_per_step(run)
    if res is None:
        return None
    return res["parts"].get(name.split(".")[1], 0.0)
