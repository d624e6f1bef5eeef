"""decode_roofline: the least time the traced decode steps could take on
the chip, each the larger of its FLOPs over the bf16 peak and its bytes
(weights once at 2 bytes a parameter, live keys and values at the
cache's bytes; ``bench/flops.py``) over the HBM bandwidth, as a share of
their device time, in %."""

import trace_reduce


def read(run, name):
    red = run["trace"]
    mods = trace_reduce.decode_modules(red) if red else []
    calls = run["traced_lens"]
    pk = run["peaks"]
    if not mods or not calls or not pk:
        return None
    fl = run["flops"]
    least = sum(max(fl.decode_step_flops(run["sizes"], lens)
                    / pk["flops_bf16_per_s"],
                    fl.decode_step_bytes(run["sizes"], lens, run["kv_bytes"])
                    / pk["hbm_bytes_per_s"])
                for lens in calls[:len(mods)])
    t = sum(m["end"] - m["start"] for m in mods[:len(calls)])
    return 100.0 * least / t
