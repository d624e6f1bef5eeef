"""decode_mfu: model FLOPs of the traced decode steps (2 per weight of
every matrix product for each active row, plus attention over each row's
live length; ``decode_step_flops`` of the configuration's module,
``bench/reference/<arch>.py``) over their device time times the
chip's bf16 peak (``bench/peaks.json``), in %."""

import trace_reduce


def read(run, name):
    red = run["trace"]
    mods = trace_reduce.decode_modules(red) if red else []
    calls = run["traced_lens"]
    if not mods or not calls or not run["peaks"]:
        return None
    f = sum(run["arch"].decode_step_flops(run["sizes"], lens)
            for lens in calls[:len(mods)])
    t = sum(m["end"] - m["start"] for m in mods[:len(calls)])
    return 100.0 * f / (t * run["peaks"]["flops_bf16_per_s"])
