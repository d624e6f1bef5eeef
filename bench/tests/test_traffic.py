"""The traffic generator: deterministic from the seed, the same work for
every seed, and mixes found by file name."""

import json
import os

import numpy as np
import pytest

import run
import traffic

BIG = 2 ** 31 + 2 ** 33 + 12345      # wider than 32 signed bits


@pytest.mark.parametrize("mix", ["chat", "offline", "prefill"])
def test_same_seed_same_requests(mix):
    m = traffic.load_mix(mix)
    a = traffic.generate(m, BIG, 30, 49155)
    b = traffic.generate(m, BIG, 30, 49155)
    assert len(a) == len(b) == traffic.request_count(m, 30)
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", ["chat", "offline", "prefill"])
def test_seeds_change_order_not_work(mix):
    m = traffic.load_mix(mix)
    a = traffic.generate(m, 1, 30, 49155)
    b = traffic.generate(m, BIG, 30, 49155)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert [x.max_new for x in a] != [x.max_new for x in b]
    if "schedule_seed" not in m.get("arrival", {}):
        assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    # the gaps, the one from the last arrival to the close included
    ga = np.diff([x.arrival_s for x in a] + [30.0])
    gb = np.diff([x.arrival_s for x in b] + [30.0])
    np.testing.assert_allclose(sorted(ga), sorted(gb), rtol=1e-9, atol=1e-9)
    # and the window itself holds the same requests, by number and size
    wa = [(len(x.prompt), x.max_new) for x in a if x.arrival_s >= 0]
    wb = [(len(x.prompt), x.max_new) for x in b if x.arrival_s >= 0]
    assert sorted(p for p, _ in wa) == sorted(p for p, _ in wb)
    assert sorted(n for _, n in wa) == sorted(n for _, n in wb)


def test_chat_lengths_and_arrivals():
    m = traffic.load_mix("chat")
    items = traffic.generate(m, 7, 30, 49155)
    buckets = set(m["prompt_len"]["buckets"])
    assert {len(x.prompt) for x in items} <= buckets
    assert all(16 <= x.max_new <= 256 for x in items)
    assert all(len(x.prompt) + x.max_new <= m["engine"]["max_ctx"]
               for x in items)
    t = [x.arrival_s for x in items]
    assert t == sorted(t)
    assert t[0] == pytest.approx(-m["arrival"]["preroll_s"])
    assert t[-1] < 30
    # the arrivals inside the window keep the configured rate
    inside = sum(1 for x in t if 0 <= x < 30)
    assert inside == round(m["arrival"]["rate_per_s"] * 30)
    med = np.median([len(x.prompt) for x in items])
    assert 128 <= med <= 256


def test_prefill_lengths_and_arrivals():
    m = traffic.load_mix("prefill")
    items = traffic.generate(m, 11, 30, 49155)
    lens = [len(x.prompt) for x in items]
    # every bucket that set-up warms is sent, in equal shares
    assert set(lens) == set(traffic.prompt_lengths(m)) == {
        1024, 1280, 1536, 1792, 2040}
    counts = [lens.count(v) for v in set(lens)]
    assert max(counts) - min(counts) <= 2
    assert {x.max_new for x in items} == {1, 2, 3, 4}
    assert all(len(x.prompt) + x.max_new <= m["engine"]["max_ctx"]
               for x in items)
    inside = sum(1 for x in items if 0 <= x.arrival_s < 30)
    assert inside == round(m["arrival"]["rate_per_s"] * 30) >= 150


@pytest.mark.parametrize("mix", ["chat", "prefill"])
def test_a_schedule_seed_fixes_the_arrival_trace(mix):
    # with a schedule_seed every run replays one trace (arrival times and
    # prompt lengths); the run's seed still draws the answer lengths'
    # order and the tokens.  Without one the seed orders everything.
    m = traffic.load_mix(mix)
    m["arrival"].setdefault("schedule_seed", 15)
    a, b = (traffic.generate(m, s, 30, 49155) for s in (5, BIG))
    assert [x.arrival_s for x in a] == [x.arrival_s for x in b]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.max_new for x in a] != [x.max_new for x in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another schedule_seed is another trace of the same work
    c = traffic.generate(dict(m, arrival=dict(m["arrival"],
                                              schedule_seed=16)),
                         5, 30, 49155)
    assert [x.arrival_s for x in c] != [x.arrival_s for x in a]
    assert sorted(len(x.prompt) for x in c) == sorted(len(x.prompt)
                                                      for x in a)
    free = dict(m, arrival={k: v for k, v in m["arrival"].items()
                            if k != "schedule_seed"})
    f1, f2 = (traffic.generate(free, s, 30, 49155) for s in (5, BIG))
    assert [x.arrival_s for x in f1] != [x.arrival_s for x in f2]


def test_a_schedule_seed_leaves_other_mixes_alone():
    # the chat mix names none: its requests are those the seed alone draws
    m = traffic.load_mix("chat")
    assert "schedule_seed" not in m["arrival"]
    rng = np.random.default_rng(BIG)
    n = traffic.stretches(m, 30)[0][2]
    want = -6.0 + traffic.arrivals(m, n, 6.0, rng)
    got = [x.arrival_s for x in traffic.generate(m, BIG, 30, 49155)][:n]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


def test_offline_backlog():
    m = traffic.load_mix("offline")
    items = traffic.generate(m, 3, 30, 256000)
    assert len(items) == m["engine"]["max_batch"] + 12 * 30
    assert {len(x.prompt) for x in items} == {32, 64, 128}
    assert min(x.max_new for x in items) >= 128
    assert max(x.max_new for x in items) <= 512
    assert all(x.arrival_s == 0 for x in items)


def test_dropped_in_mix_is_found_by_name():
    name = "zz_dummy_test_mix"
    path = os.path.join(traffic.TRAFFIC_DIR, f"{name}.json")
    mix = dict(traffic.load_mix("chat"))
    mix["arrival"] = dict(mix["arrival"], rate_per_s=1.0)
    with open(path, "w") as f:
        json.dump(mix, f)
    try:
        bench = run.load_benchmark()
        bench["workloads"].append({"name": f"granite_3_2b.{name}",
                                   "config": "granite_3_2b",
                                   "traffic": name, "chips": 1})
        limits = os.path.join(run.BENCH, "limits",
                              f"granite_3_2b.{name}.json")
        with open(limits, "w") as f:
            json.dump({"logit_gap": 1, "logprob_err": 1,
                       "logprob_ff_err": 1}, f)
        try:
            parts = run.resolve(bench, f"granite_3_2b.{name}")
        finally:
            os.remove(limits)
        assert parts["mix"]["arrival"]["rate_per_s"] == 1.0
        assert len(traffic.generate(parts["mix"], 5, 10, 100)) == 16
    finally:
        os.remove(path)


def test_unknown_mix_is_an_error():
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("no_such_mix")
