"""decode_step_ms: device time of one execution of the decode program,
the mean over the traced window, in ms."""

import trace_reduce


def read(run, name):
    red = run["trace"]
    mods = trace_reduce.decode_modules(red) if red else []
    if not mods:
        return None
    return 1e3 * sum(m["end"] - m["start"] for m in mods) / len(mods)
