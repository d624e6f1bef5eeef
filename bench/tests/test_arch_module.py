"""A configuration's architecture comes from its own module: the harness
takes the weights, the shape numbers, both decode counts and the
reference from ``bench/reference/<reference>.py`` and from nowhere else,
and builds the program's ``ModelConfig`` from the file's keys and its
``program.fields``."""

import copy
import json
import os
import types

import jax
import pytest

import run
import tiny
import trace_reduce

SEED = 2 ** 34 + 5
GRANITE = os.path.join(run.BENCH, "configs", "granite_3_2b.json")


def granite():
    with open(GRANITE) as f:
        return json.load(f)


def spy_module(calls):
    """``dense_gqa`` with every export the harness reads counting its
    calls."""
    real = run._reference("dense_gqa")
    spy = types.SimpleNamespace()
    for name in ("sizes", "make_weights", "decode_step_flops",
                 "decode_step_bytes", "score"):
        def wrapped(*a, _name=name, **k):
            calls[_name] = calls.get(_name, 0) + 1
            return getattr(real, _name)(*a, **k)
        setattr(spy, name, wrapped)
    return spy


def test_cell_takes_its_architecture_from_its_module(monkeypatch):
    calls, asked = {}, []
    spy = spy_module(calls)

    def resolve_reference(name):
        asked.append(name)
        return spy
    monkeypatch.setattr(run, "_reference", resolve_reference)
    # the harness's look for a chip gives the host's devices and the v5e
    # peaks, and every traced decode execution 1 ms of device time, so
    # that the decode readers read on the CPU
    with open(os.path.join(run.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "devices": jax.devices(), "peaks": peaks})
    monkeypatch.setattr(trace_reduce, "decode_modules", lambda red: [
        {"name": "jit_step_decode", "start": 0.0, "end": 1e-3}] * 10 ** 6)
    bench = run.load_benchmark()
    bench = dict(bench, per_layer=[
        {"name": "decode_mfu.chat", "unit": "%"},
        {"name": "decode_roofline.chat", "unit": "%"}])
    parts = tiny.parts("open_loop")
    res = run.run_cell("granite_3_2b.chat", SEED, 2.0, True, bench=bench,
                       parts=parts)
    assert res["correct"], res["checks"]
    assert asked and set(asked) == {"dense_gqa"}
    # weights for the program and again for the reference, the shape
    # numbers, both counts for the readers, and the reference's scores
    assert calls["make_weights"] == 2
    assert calls["sizes"] >= 1
    assert calls["decode_step_flops"] >= 2
    assert calls["decode_step_bytes"] >= 1
    assert calls["score"] >= 1
    assert set(res["metrics"]) == {"decode_mfu.chat", "decode_roofline.chat"}


def test_run_names_no_architecture():
    with open(os.path.join(run.BENCH, "run.py")) as f:
        src = f.read()
    for word in ("import weights", "import flops", "weights_mod",
                 "flops_mod", "dense_gqa", "granite", "minitron"):
        assert word not in src


def test_program_fields_set_model_config_fields():
    conf = granite()
    conf["program"] = dict(conf["program"], fields={
        "attn_block_q": 256, "moe_capacity_factor": 2.0,
        "moe_num_experts": 8, "moe_top_k": 2})
    cfg = run.model_config(conf)
    # a knob of how the program runs may go below the full-size value
    assert cfg.attn_block_q == 256 and cfg.moe_capacity_factor == 2.0
    assert cfg.moe_num_experts == 8 and cfg.moe_top_k == 2
    assert cfg.num_layers == 40 and cfg.d_model == 2048


def test_program_fields_refuse_an_unknown_field():
    conf = granite()
    conf["program"] = dict(conf["program"], fields={"num_experts": 8})
    with pytest.raises(ValueError, match="does not have"):
        run.model_config(conf)


def test_a_cut_must_be_listed_in_reduced():
    conf = granite()
    conf["program"] = dict(conf["program"], fields={"num_layers": 20})
    with pytest.raises(ValueError, match="num_layers"):
        run.model_config(conf)
    conf["reduced"] = ["num_layers"]
    assert run.model_config(conf).num_layers == 20
    conf = granite()
    conf["num_hidden_layers"] = 12
    with pytest.raises(ValueError, match="num_hidden_layers"):
        run.model_config(conf)


def test_published_keys_apply_where_the_file_has_them():
    from repro.configs import get_config
    conf = granite()
    del conf["head_dim"], conf["intermediate_size"]
    cfg = run.model_config(conf)
    base = get_config("granite_3_2b")
    assert cfg.head_dim == base.head_dim and cfg.d_ff == base.d_ff
    assert cfg.rope_theta == 10000.0 and cfg.tie_embeddings is False
    changed = copy.deepcopy(granite())
    changed["program"]["fields"] = {"d_ff": 9000}
    assert run.model_config(changed).d_ff == 9000     # fields come last
