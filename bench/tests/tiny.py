"""A cell small enough to run on the CPU: Granite's layout at toy sizes."""

import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parts(kind: str = "open_loop", policy: str = "ff_master") -> dict:
    with open(os.path.join(BENCH, "configs", "granite_3_2b.json")) as f:
        conf = json.load(f)
    cut = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, intermediate_size=256,
               vocab_size=512)
    conf.update(cut, reduced=sorted(cut))
    conf["program"] = dict(conf["program"], policy=policy)
    engine = {"max_batch": 4, "max_ctx": 64, "page_size": 8,
              "sync_every": 1}
    if kind == "open_loop":
        mix = {"kind": "open_loop",
               "arrival": {"process": "poisson", "rate_per_s": 6.0,
                           "preroll_s": 0.5},
               "prompt_len": {"dist": "lognormal", "median": 12,
                              "sigma": 0.5, "buckets": [8, 16, 24]},
               "output_len": {"dist": "lognormal", "median": 8,
                              "sigma": 0.5, "min": 4, "max": 16},
               "engine": engine, "trace_s": 0.5, "check_requests": 3}
    elif kind == "prefill":
        # the prefill mix's shape: long prompts, one to four new tokens,
        # one fixed arrival trace
        mix = {"kind": "open_loop",
               "arrival": {"process": "poisson", "rate_per_s": 8.0,
                           "preroll_s": 0.5, "schedule_seed": 15},
               "prompt_len": {"dist": "choice", "values": [32, 48, 56]},
               "output_len": {"dist": "uniform_int", "min": 1, "max": 4},
               "engine": engine, "trace_s": 0.5, "check_requests": 3}
    else:
        mix = {"kind": "backlog", "backlog_per_s": 4,
               "prompt_len": {"dist": "choice", "values": [8, 16]},
               "output_len": {"dist": "uniform_int", "min": 6, "max": 16},
               "engine": engine, "trace_s": 0.5, "check_requests": 3}
    return {"config": conf, "mix": copy.deepcopy(mix),
            "limits": {"logit_gap": 0.05, "logprob_err": 0.05,
                       "logprob_ff_err": 0.05}}
