"""The readers of the engine's own spans and parts (``prefill_ms``,
``step_host_ms``, ``part_ms``) and the shared clock (``obs_clock``), on
hand-made inputs where every number is known, and on a trace recorded on
a TPU v5e with the renamed, scoped programs
(``data/engine_trace_scoped.xplane.pb``, made by
``bench/tools/record_trace_sample.py --out``, with the engine's recorder
events and the decode program's part map beside it in
``data/engine_trace_scoped.obs.json``)."""

import json
import os

import pytest

import obs_clock
import run
from repro.obs.parts import program_parts

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "engine_trace_scoped.xplane.pb")
SCOPED_OBS = os.path.join(DATA, "engine_trace_scoped.obs.json")


def _x(name, ts, dur, tid=0, **args):
    ev = {"ph": "X", "name": name, "ts": float(ts), "dur": float(dur),
          "tid": tid}
    if args:
        ev["args"] = args
    return ev


def test_prefill_ms_reads_request_prefill_spans_in_the_window():
    events = [_x("prefill", 0, 50e3, tid=1),          # ends in the window
              _x("prefill", 100e3, 30e3, tid=2),
              _x("prefill", 900e3, 200e3, tid=3),     # ends after it
              _x("serve.prefill", 0, 90e3)]           # engine span: not read
    run_ = {"engine_events": events, "obs_window": (10e3, 1000e3)}
    read = run.load_reader("prefill_ms.p90")
    assert read(run_, "prefill_ms.p90") == pytest.approx(30 + 0.9 * 20)
    assert read({"engine_events": [], "obs_window": (0, 1)},
                "prefill_ms.p90") is None


def test_step_host_ms_leaves_out_the_waits():
    events = [_x("serve.step", 0, 10e3),
              _x("serve.prefill.wait", 500, 500),
              _x("serve.flush", 1500, 7e3),
              _x("serve.flush.wait", 2e3, 6e3),
              _x("serve.step", 20e3, 4e3),
              _x("serve.flush.wait", 21e3, 2e3),
              _x("serve.step", 90e3, 4e3),             # ends after the window
              _x("serve.flush.wait", 91e3, 1e3)]
    run_ = {"engine_events": events, "obs_window": (0, 50e3), "trace": None}
    read = run.load_reader("step_host_ms.chat")
    # (10 - 6 - 0.5) and (4 - 2) ms
    assert read(run_, "step_host_ms.chat") == pytest.approx((3.5 + 2) / 2)
    assert read({"engine_events": events[1:2], "obs_window": (0, 50e3),
                 "trace": None}, "step_host_ms.chat") is None


HLO = """HloModule jit_step_decode, is_scheduled=true

%fused_computation (p0: f32[4]) -> bf16[4] {
  %p0 = f32[4]{0} parameter(0)
  %convert.1 = bf16[4]{0} convert(%p0), metadata={op_name="jit(step_decode)/while/body/closed_call/mlp/cast/convert_element_type"}
  ROOT %dot.1 = bf16[4]{0} multiply(%convert.1, %convert.1), metadata={op_name="jit(step_decode)/while/body/closed_call/mlp/dot_general"}
}

ENTRY %main (w: f32[40,4], p: f32[4]) -> bf16[4] {
  %w = f32[40,4]{1,0} parameter(0)
  %p = f32[4]{0} parameter(1)
  %convert.7 = bf16[40,4]{1,0} convert(%w), metadata={op_name="jit(step_decode)/while/body/closed_call/attn/cast/convert_element_type"}
  %fusion.1 = bf16[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_decode)/while/body/closed_call/mlp/dot_general"}
  %scatter.3 = bf16[4]{0} add(%fusion.1, %fusion.1), metadata={op_name="jit(step_decode)/while/body/closed_call/kv/scatter"}
  %while.1 = bf16[4]{0} while(%scatter.3), condition=%c, body=%b, metadata={op_name="jit(step_decode)/while"}
  ROOT %dot.9 = bf16[4]{0} multiply(%while.1, %while.1), metadata={op_name="jit(step_decode)/head/dot_general"}
}
"""


def _op(name, start, end, dev="/device:TPU:0"):
    return {"name": f"%{name} = bf16[4]{{0}} op(...)", "start": start,
            "end": end, "device": dev}


def _parts_run(with_map=True):
    module, parts = program_parts(HLO)
    mods = [{"name": "jit_step_decode(7)", "start": 0.0, "end": 100.0,
             "device": "/device:TPU:0", "kind": "serve.decode_step"},
            {"name": "jit_step_decode(7)", "start": 200.0, "end": 300.0,
             "device": "/device:TPU:0", "kind": "serve.decode_step"},
            {"name": "jit_step_prefill(8)", "start": 400.0, "end": 500.0,
             "device": "/device:TPU:0", "kind": "serve.prefill"}]
    ops = []
    for base in (0.0, 200.0):
        ops += [_op("convert.7", base, base + 10),
                _op("while.1", base + 10, base + 90),      # encloses the body
                _op("fusion.1", base + 10, base + 50),     # convert fused in
                _op("scatter.3", base + 50, base + 70),
                _op("copy.5", base + 70, base + 90),       # not in the map
                _op("dot.9", base + 90, base + 98)]
    ops += [_op("fusion.1", 400.0, 480.0)]                 # a prefill op
    red = {"modules": mods, "ops": ops, "host": [],
           "window": {"start": 0.0, "end": 1000.0},
           "devices": ["/device:TPU:0"]}
    events = [{"ph": "M", "name": "program", "ts": 0, "tid": 0,
               "args": {"name": module, "parts": parts}}] if with_map else []
    return {"trace": red, "engine_events": events}


def test_part_ms_counts_leaf_ops_of_decode_executions():
    run_ = _parts_run()
    read = run.load_reader("part_ms.cast.chat")
    got = {p: read(run_, f"part_ms.{p}.chat")
           for p in ("cast", "attn", "mlp", "kv", "head", "sample")}
    # per decode execution: convert 10 (cast), the while's body ops and not
    # the while itself, the fused convert with its consumer (mlp 40), the
    # prefill's fusion.1 nowhere, ms per step from seconds
    assert got == pytest.approx({"cast": 1e4, "attn": 0.0, "mlp": 4e4,
                                 "kv": 2e4, "head": 8e3, "sample": 0.0})
    res = run_["part_ms"]
    assert res["steps"] == 2
    assert res["parts"]["other"] == pytest.approx(2e4)     # copy.5
    assert res["other"][0][0] == "copy.5"
    assert res["cover"] == pytest.approx(78 / 100)
    assert sum(res["parts"].values()) <= 1e3 * 100         # <= the step


def test_part_ms_finds_nothing_without_the_map():
    run_ = _parts_run(with_map=False)
    assert run.load_reader("part_ms.cast.chat")(
        run_, "part_ms.cast.chat") is None


def test_obs_clock_matches_the_traced_stretch():
    # evenly spaced steps whose lengths differ, as a server's do
    lens = (50e3, 52e3, 49e3, 51e3, 50e3, 53e3)
    rec = [_x("serve.step", i * 100e3, d) for i, d in enumerate(lens)]
    rec.append(_x("serve.flush", 10e3, 5e3))
    # the trace saw steps 2-4, its clock 7 s ahead, with some jitter
    red = {"host": [{"name": "serve.step", "start": 7.0 + 0.1 * i + j,
                     "end": 7.0 + 0.1 * i + lens[i] * 1e-6}
                    for i, j in ((2, 0.0), (3, 1e-5), (4, -2e-5))]
           + [{"name": "bench.step", "start": 7.0, "end": 7.1}]}
    clock = obs_clock.align(rec, red)
    assert clock["pairs"] == 3
    assert clock["offset_s"] == pytest.approx(7.0, abs=2e-5)
    assert clock["max_err_s"] <= 3e-5
    assert obs_clock.to_trace(300e3, clock) == pytest.approx(7.3, abs=3e-5)
    assert obs_clock.align(rec[:2], red) is None


# -- the trace recorded on the chip ------------------------------------------

@pytest.fixture(scope="module")
def scoped():
    if not os.path.exists(SCOPED):
        pytest.skip("no scoped trace recorded on the chip yet")
    import trace_reduce
    with open(SCOPED_OBS) as f:
        rec = json.load(f)
    red = trace_reduce.reduce_trace(SCOPED)
    events = rec["events"] + [{"ph": "M", "name": "program", "ts": 0,
                               "tid": 0, "args": rec["program"]}]
    return {"trace": red, "engine_events": events}


def test_scoped_programs_are_named(scoped):
    import trace_reduce
    red = scoped["trace"]
    names = {m["name"].split("(")[0] for m in red["modules"]
             if m["kind"] in ("serve.prefill", "serve.decode_step")
             and m["name"].startswith("jit_step")}
    assert names == {"jit_step_decode", "jit_step_prefill"}
    dec = trace_reduce.decode_modules(red)
    assert dec and all(m["name"].startswith("jit_step_decode") for m in dec)
    host = {h["name"] for h in red["host"]}
    assert {"serve.step", "serve.admit", "serve.prefill",
            "serve.prefill.wait", "serve.decode_prep", "serve.decode_step",
            "serve.flush", "serve.flush.wait"} <= host


def test_scoped_parts_cover_the_decode_program(scoped):
    read = run.load_reader("part_ms")
    for p in ("cast", "kv", "head", "sample"):
        assert read(scoped, f"part_ms.{p}.x") > 0, p
    res = scoped["part_ms"]
    # at this toy size (62 us a step) the gaps between tiny operations are
    # a sixth of the program's time, so the named share is checked against
    # the operations' own time; the cells' cover is in PERF.md
    named = sum(v for k, v in res["parts"].items() if k != "other")
    assert named >= 0.9 * sum(res["parts"].values())
    assert res["cover"] <= 1.0
    import trace_reduce
    dec = trace_reduce.decode_modules(scoped["trace"])
    step_ms = 1e3 * sum(m["end"] - m["start"] for m in dec) / len(dec)
    assert sum(res["parts"].values()) <= step_ms


def test_scoped_clock_aligns(scoped):
    clock = obs_clock.align(scoped["engine_events"], scoped["trace"])
    assert clock is not None and clock["pairs"] >= 3
    assert clock["max_err_s"] <= 0.5e-3
