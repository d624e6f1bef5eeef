"""The DeepSeek-V2 cell at toy sizes on the CPU: the harness runs the
configuration through its own module (``bench/reference/
deepseek_v2.py``) and the normal engine path, a sound run is ``correct``,
the fp8 control is not, nor a run whose decode step alters a token; the
new readers read the engine's ``moe`` counter and the trace's parts.

The toy keeps the published routing (softmax over every routed expert,
group-limited top-k, gates x16) and the held share of one group; widths
and depth are cut so that a run takes seconds.  The limits lie above
what sound runs of this size read (logit gaps 0-0.03, logprob errors up
to 0.08 on the CPU) and below the control's (above 1)."""

import copy
import json
import os

import pytest

import run

SEED = 2 ** 35 + 3
CELL = "deepseek_v2.offline_long"
LIMITS = {"logit_gap": 0.08, "logprob_err": 0.1, "logprob_ff_err": 0.1}
CUT = {"num_hidden_layers": 3, "hidden_size": 64,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "intermediate_size": 96, "vocab_size": 256, "n_routed_experts": 4}
FIELDS = {"q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_d_ff": 32,
          "moe_num_experts": 16, "moe_top_k": 3, "moe_n_group": 4,
          "moe_topk_group": 2, "moe_held_experts": 4, "moe_held_offset": 4,
          "attn_block_q": 16, "attn_block_kv": 16}


def parts():
    with open(os.path.join(run.BENCH, "configs", "deepseek_v2.json")) as f:
        conf = json.load(f)
    conf.update(CUT, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
                num_experts_per_tok=3, n_group=4, topk_group=2,
                published={"n_routed_experts": 16},
                deployment={"held_offset": 4})
    conf["reduced"] = sorted(set(CUT) | set(FIELDS))
    conf["program"] = dict(conf["program"], fields=dict(
        conf["program"]["fields"], **FIELDS))
    mix = {"kind": "backlog", "backlog_per_s": 4,
           "prompt_len": {"dist": "choice", "values": [8, 16]},
           "output_len": {"dist": "uniform_int", "min": 6, "max": 16},
           "engine": {"max_batch": 4, "max_ctx": 40, "page_size": 8,
                      "sync_every": 1},
           "trace_s": 0.5, "check_requests": 3}
    return {"config": conf, "mix": copy.deepcopy(mix),
            "limits": dict(LIMITS)}


def cell(control=False, **kw):
    return run.run_cell(CELL, SEED, 2.0, False, parts=parts(),
                        need_chip=False, control=control, **kw)


def test_the_cell_runs_the_configuration_on_its_mix():
    bench = run.load_benchmark()
    got = run.resolve(bench, CELL)
    assert got["config"]["reference"] == "deepseek_v2"
    mix = got["mix"]
    assert (mix["kind"], mix["backlog_per_s"], mix["check_requests"]) == \
        ("backlog", 16, 6)
    assert mix["prompt_len"]["values"] == [128, 256, 512]
    assert (mix["output_len"]["min"], mix["output_len"]["max"]) == (256, 768)
    assert mix["engine"] == {"max_batch": 128, "max_ctx": 1280,
                             "page_size": 16, "sync_every": 1}
    assert set(got["limits"]) & set(run.CHECKED) == {"logit_gap"}
    traced = {m["name"] for m in run.cell_metrics(bench, CELL, True)}
    assert {f"{n}.offline_long" for n in (
        "part_ms.moe", "part_ms.attn", "part_ms.kv", "moe_roofline",
        "mla_roofline", "moe_tokens")} <= traced
    assert [m["name"] for m in run.cell_metrics(bench, CELL, False)] == \
        ["output_tokens_per_s", "setup_s"]


def test_the_configuration_is_the_published_model_cut_to_one_chip():
    with open(os.path.join(run.BENCH, "configs", "deepseek_v2.json")) as f:
        conf = json.load(f)
    cfg = run.model_config(conf)
    ref = run._reference("deepseek_v2")
    s = ref.sizes(conf)
    assert (cfg.num_layers, cfg.first_k_dense_replace) == (6, 1)
    assert cfg.moe_held == (0, 20) and cfg.moe_num_experts == 160
    assert (s["E"], s["held"], s["k"], s["groups"]) == (160, 20, 6, 8)
    assert cfg.param_dtype == cfg.compute_dtype == "bfloat16"
    c = ref.param_counts(s)
    assert round(c["mla"] / 1e6, 1) == 149.2
    assert round(c["held"] / 1e6, 1) == 471.9
    assert round(c["total"] / 1e9, 2) == 4.73


def test_sound_run_is_correct_and_the_control_is_not():
    res = cell(control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
    ctl = res["control"]
    assert ctl["correct"] is False, ctl["checks"]


def test_an_altered_token_is_not_correct(monkeypatch):
    from repro.serve import engine as eng_mod
    make = eng_mod.ServeEngine._make_decode_step

    def broken(self):
        step = make(self)
        V = self.cfg.vocab_size

        def wrapped(*args):
            out = step(*args)
            return ((out[0] + 1) % V,) + tuple(out[1:])
        return wrapped
    monkeypatch.setattr(eng_mod.ServeEngine, "_make_decode_step", broken)
    res = cell()
    assert not res["correct"], res["checks"]


def _trace_run(steps, lens, parts_ms):
    """A run as the readers see it: ``steps`` moe counter args, one traced
    decode execution per step of 10 ms, and the part map's times."""
    conf = parts()["config"]
    ref = run._reference("deepseek_v2")
    with open(os.path.join(run.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    events = [{"ph": "C", "name": "moe", "ts": 10.0 + i, "args": a}
              for i, a in enumerate(steps)]
    mods = [{"name": "jit_step_decode", "kind": "serve.decode_step",
             "start": i * 0.01, "end": (i + 1) * 0.01, "device": "d"}
            for i in range(len(steps))]
    return {"trace": {"modules": mods, "ops": [], "window": None},
            "engine_events": events, "obs_window": (0.0, 1e9),
            "traced_lens": [lens] * len(steps), "peaks": peaks,
            "sizes": ref.sizes(conf), "arch": ref, "kv_bytes": 2,
            "part_ms": {"parts": parts_ms, "cover": 1.0, "steps": len(steps),
                        "other": []}}


def test_moe_readers_on_synthetic_counts():
    ref = run._reference("deepseek_v2")
    steps = [{"pairs": 24, "hit": 6, "max": 3, "layers": 2, "held": 4},
             {"pairs": 16, "hit": 8, "max": 2, "layers": 2, "held": 4}]
    r = _trace_run(steps, [9, 12, 20], {"moe": 2.0, "attn": 1.0, "kv": 1.0})
    assert run.load_reader("moe_tokens.offline_long")(
        r, "moe_tokens.offline_long") == pytest.approx((3 + 2) / 2)
    s, pk = r["sizes"], r["peaks"]
    c = ref.param_counts(s)
    least = 0.0
    for a in steps:
        byts = 2.0 * (2 * (c["router"] + c["shared"]) + a["hit"]
                      * c["expert"])
        flops = 2.0 * (a["pairs"] * c["expert"]
                       + 3 * 2 * (c["router"] + c["shared"]))
        least += max(flops / pk["flops_bf16_per_s"],
                     byts / pk["hbm_bytes_per_s"])
    got = run.load_reader("moe_roofline.offline_long")(
        r, "moe_roofline.offline_long")
    assert got == pytest.approx(100 * least / (2 * 2e-3))
    mla = run.load_reader("mla_roofline.offline_long")(
        r, "mla_roofline.offline_long")
    assert 0 < mla < 100
    # a dense configuration's run has no moe events and no latent sizes
    dense = dict(r, engine_events=[], sizes={"L": 2})
    for name in ("moe_tokens", "moe_roofline", "mla_roofline"):
        assert run.load_reader(f"{name}.offline_long")(
            dense, f"{name}.offline_long") is None
