"""The traffic generator: deterministic from the seed, the same work for
every seed, and mixes found by file name."""

import json
import os

import numpy as np
import pytest

import run
import traffic

BIG = 2 ** 31 + 2 ** 33 + 12345      # wider than 32 signed bits


@pytest.mark.parametrize("mix", ["chat", "offline"])
def test_same_seed_same_requests(mix):
    m = traffic.load_mix(mix)
    a = traffic.generate(m, BIG, 30, 49155)
    b = traffic.generate(m, BIG, 30, 49155)
    assert len(a) == len(b) == traffic.request_count(m, 30)
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", ["chat", "offline"])
def test_seeds_change_order_not_work(mix):
    m = traffic.load_mix(mix)
    a = traffic.generate(m, 1, 30, 49155)
    b = traffic.generate(m, BIG, 30, 49155)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
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
