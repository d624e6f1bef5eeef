"""moe_roofline.<cell>: the least time the MoE layers of the traced decode
steps could take on the chip, as a share of the device time of the decode
program's ``moe`` part (``part_ms``), in %.

Per traced step, the larger of its FLOPs over the bf16 peak and its bytes
over the HBM bandwidth (``bench/peaks.json``).  Bytes, at 2 a weight: in
every MoE layer the router and the shared experts, and ``3 d Fe`` for each
(layer, held expert) the engine's ``moe`` counter shows hit in that step.
FLOPs: 2 per weight of an expert (``3 d Fe``) for each routed pair the
counter shows, and 2 per weight of the router and the shared experts for
each active row in every MoE layer.  The traced steps are the last decode
executions of the window, so their counts are the window's last ``moe``
events.  The counts come from the configuration's module
(``param_counts`` of ``bench/reference/<arch>.py``); a configuration
without routed experts reads nothing."""

import importlib.util
import os

import trace_reduce

_HERE = os.path.dirname(os.path.abspath(__file__))


def sibling(stem):
    """Another reader module of this directory."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{stem}", os.path.join(_HERE, f"{stem}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run, name):
    red = run["trace"]
    mods = trace_reduce.decode_modules(red) if red else []
    pk = run["peaks"]
    counts = getattr(run["arch"], "param_counts", None)
    steps = sibling("moe_tokens").moe_steps(run)
    if not mods or not pk or counts is None or not steps:
        return None
    parts = sibling("part_ms").parts_per_step(run)
    moe_ms = (parts or {}).get("parts", {}).get("moe")
    if not moe_ms:
        return None
    s = run["sizes"]
    c = counts(s)
    n_moe = s["L"] - s["n_dense"]
    rows = [len(lens) for lens in run["traced_lens"][-len(mods):]]
    steps = steps[-len(mods):]
    least = 0.0
    for a, b in zip(steps, rows):
        byts = 2.0 * (n_moe * (c["router"] + c["shared"])
                      + a["hit"] * c["expert"])
        flops = 2.0 * (a["pairs"] * c["expert"]
                       + b * n_moe * (c["router"] + c["shared"]))
        least += max(flops / pk["flops_bf16_per_s"],
                     byts / pk["hbm_bytes_per_s"])
    return 100.0 * least / (len(steps) * moe_ms * 1e-3)
