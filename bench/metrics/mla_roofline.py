"""mla_roofline.<cell>: the least time the latent attention of the traced
decode steps could take on the chip, as a share of the device time of the
decode program's ``attn`` and ``kv`` parts (``part_ms``), in %.

Per traced step, the larger of its FLOPs over the bf16 peak and its bytes
over the HBM bandwidth.  Bytes: every layer's MLA weights once at 2 bytes,
and the live latents (``kv_lora_rank + qk_rope_head_dim`` per position and
layer at the cache's bytes) of every active row read, and its new entry
written.  FLOPs: 2 per MLA weight for each active row, and the absorbed
scores and output, ``2 H (2 r + dr)`` per layer and cached position.  The
counts come from the configuration's module (``param_counts`` and
``sizes`` of ``bench/reference/<arch>.py``); a configuration without
latent attention reads nothing."""

import importlib.util
import os

import trace_reduce

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(run, name):
    red = run["trace"]
    mods = trace_reduce.decode_modules(red) if red else []
    calls = run["traced_lens"]
    pk = run["peaks"]
    s = run["sizes"]
    counts = getattr(run["arch"], "param_counts", None)
    if not mods or not calls or not pk or counts is None or "r" not in s:
        return None
    spec = importlib.util.spec_from_file_location(
        "bench_metric_part_ms", os.path.join(_HERE, "part_ms.py"))
    part_ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(part_ms)
    parts = (part_ms.parts_per_step(run) or {}).get("parts", {})
    ms = parts.get("attn", 0.0) + parts.get("kv", 0.0)
    if not ms:
        return None
    mla = counts(s)["mla"]
    L, per_pos = s["L"], s["L"] * (s["r"] + s["dr"]) * run["kv_bytes"]
    calls = calls[-len(mods):]
    least = 0.0
    for lens in calls:
        byts = 2.0 * L * mla + per_pos * (sum(lens) + len(lens))
        flops = (2.0 * L * mla * len(lens)
                 + 2.0 * L * s["H"] * (2 * s["r"] + s["dr"]) * sum(lens))
        least += max(flops / pk["flops_bf16_per_s"],
                     byts / pk["hbm_bytes_per_s"])
    return 100.0 * least / (len(calls) * ms * 1e-3)
