"""The trace reduction on a small trace recorded on a TPU v5e
(``data/engine_trace.xplane.pb``, made by
``bench/tools/record_trace_sample.py``: the serving engine at a toy size,
five requests of 8 tokens, four rows)."""

import os
import types

import pytest

import stats
import trace_reduce

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "engine_trace.xplane.pb")


@pytest.fixture(scope="module")
def red():
    if not os.path.exists(SAMPLE):
        pytest.skip("no trace recorded on the chip yet: run "
                    "bench/tools/record_trace_sample.py on a TPU")
    return trace_reduce.reduce_trace(SAMPLE)


def test_finds_the_device_and_its_programs(red):
    assert red["devices"] and all("TPU" in d for d in red["devices"])
    assert red["modules"] and red["ops"]
    assert all(m["end"] >= m["start"] for m in red["modules"])


def test_one_decode_program_per_decode_annotation(red):
    host = [h for h in red["host"] if h["name"] == "serve.decode_step"]
    steps = [h for h in red["host"] if h["name"] == "bench.step"]
    dec = [m for m in red["modules"] if m["kind"] == "serve.decode_step"
           and m["name"].startswith("jit_step")]
    pre = [m for m in red["modules"] if m["kind"] == "serve.prefill"
           and m["name"].startswith("jit_step")]
    assert len(dec) == len(host) > 0
    assert len(pre) == 5                      # one per request
    assert len(steps) >= len(host)


def test_device_work_follows_its_dispatch(red):
    # the device and host clocks agree: each decode program runs after its
    # annotation began and before the next step's annotation begins
    host = [h for h in red["host"] if h["name"] == "serve.decode_step"]
    dec = [m for m in red["modules"] if m["kind"] == "serve.decode_step"
           and m["name"].startswith("jit_step")]
    for h, m in zip(host, dec):
        assert h["start"] <= m["start"]
        assert m["start"] - h["start"] < 0.5


def test_busy_share_is_a_share(red):
    lo = min(h["start"] for h in red["host"])
    hi = max(h["end"] for h in red["host"])
    busy = stats.union_length([(o["start"], o["end"]) for o in red["ops"]],
                              lo, hi)
    assert 0 < busy < hi - lo


# -- the same reduction on a hand-made trace, where every number is known --

class _E:
    def __init__(self, name, start_ns, dur_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, dur_ns


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _fake():
    host = _P("/host:CPU", [_L("python", [
        _E("bench.window", 0, 1000),
        _E("bench.step", 10, 300), _E("serve.prefill", 20, 50),
        _E("serve.decode_step", 100, 20),
        _E("bench.step", 400, 300), _E("serve.decode_step", 410, 20),
        _E("other.span", 500, 5)])])
    dev = _P("/device:TPU:0", [
        _L("XLA Modules", [_E("jit_step(1)", 30, 60),
                           _E("jit__lambda_(2)", 95, 4),
                           _E("jit_step(1)", 110, 150),
                           _E("jit_step(1)", 420, 150)]),
        _L("XLA Ops", [_E("fusion.1", 30, 60), _E("fusion.2", 110, 100),
                       _E("fusion.3", 210, 50), _E("fusion.3", 420, 150)])])
    return types.SimpleNamespace(planes=[host, dev])


def test_hand_made_trace():
    red = trace_reduce.reduce_profile(_fake())
    assert red["devices"] == ["/device:TPU:0"]
    assert red["window"]["start"] == 0.0
    assert red["window"]["end"] == pytest.approx(1e-6)
    assert [m["kind"] for m in red["modules"]] == [
        "serve.prefill", "serve.prefill", "serve.decode_step",
        "serve.decode_step"]
    dec = trace_reduce.decode_modules(red)
    assert [round((m["end"] - m["start"]) * 1e9) for m in dec] == [150, 150]
    assert "other.span" not in {h["name"] for h in red["host"]}
    busy = stats.union_length([(o["start"], o["end"]) for o in red["ops"]],
                              0.0, 1e-6)
    assert busy == pytest.approx(360e-9)
