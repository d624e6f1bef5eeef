"""Percentiles, rates and interval arithmetic, censored requests
included."""

import numpy as np
import pytest

import stats


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy(q):
    x = np.random.default_rng(0).lognormal(size=101)
    assert stats.percentile(x, q) == pytest.approx(np.percentile(x, q))


def test_percentile_of_nothing():
    assert stats.percentile([], 90) is None


def test_ttft_counts_censored_requests():
    due = [0.0, 1.0, 2.0, 9.0, 11.0]
    first = [0.5, None, 12.0, 9.25, None]
    # due at 11 is after the close and does not count; the one with no
    # token and the one served after the close count their wait so far
    assert stats.ttfts(due, first, 10.0) == [0.5, 9.0, 8.0, 0.25]


def test_tail_sees_a_stall_at_the_close():
    due = [float(i) for i in range(10)]
    served = [d + 0.1 for d in due[:8]] + [None, None]
    t = stats.ttfts(due, served, 10.0)
    assert stats.percentile(t, 90) > 1.0


def test_gaps_and_counts_in_window():
    times = [0.5, 1.0, 1.0, 2.0, 3.5, 4.0]
    assert stats.gaps_in_window(times, 1.0, 3.6) == [0.5, 0.0, 1.0, 1.5]
    assert stats.count_in_window(times, 1.0, 3.6) == 4


def test_output_rate_reader():
    import importlib.util
    import os
    import types
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(stats.__file__.rsplit("/", 1)[0], "metrics",
                          "output_tokens_per_s.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    recs = [types.SimpleNamespace(times=[0.5, 1.5, 2.5]),
            types.SimpleNamespace(times=[1.0, 1.0, 3.9, 4.1])]
    run = {"recs": recs, "window": {"open": 1.0, "close": 4.0}}
    assert mod.read(run, "output_tokens_per_s") == pytest.approx(5 / 3)


def test_ttft_p90_ms_reader():
    import types
    import run
    # due times and first tokens: two due before the window, one after
    # its close, one served after the close and one never served
    due = [0.5, 1.0, 1.5, 2.0, 2.5, 3.0, 3.5, 4.5, 5.0]
    first = [0.6, 1.2, 1.6, 2.4, 2.6, 3.1, None, 5.5, 5.1]
    recs = [types.SimpleNamespace(due=d, times=[] if f is None else [f])
            for d, f in zip(due, first)]
    run_ = {"recs": recs, "window": {"open": 1.0, "close": 5.0}}
    # in the window: 0.2, 0.1, 0.4, 0.1, 0.1, 1.5 (waited to the close),
    # 0.5 (served after the close: counts to the close)
    waits = sorted([0.2, 0.1, 0.4, 0.1, 0.1, 1.5, 0.5])
    want = 1e3 * (waits[5] + 0.4 * (waits[6] - waits[5]))
    # one reader, bench/metrics/ttft_ms.py, serves both names
    read = run.load_reader("ttft_p90_ms")
    assert read.__module__ == run.load_reader("ttft_ms.p90").__module__ \
        == "bench_metric_ttft_ms"
    assert read(run_, "ttft_p90_ms") == pytest.approx(want)
    assert read(run_, "ttft_ms.p90") == pytest.approx(want)
    assert read({"recs": [], "window": run_["window"]},
                "ttft_p90_ms") is None


@pytest.mark.parametrize("name,q", [
    ("prefill_ms.p90", 90), ("queue_wait_ms.p90.prefill", 90),
    ("prefill_ms.p50.prefill", 50), ("queue_wait_ms", 50),
    ("prefill_ms.prefill", 50), ("ttft_p90_ms", 90), ("itl_p95_ms", 95),
    ("step_host_ms.prefill", 50)])
def test_percentile_from_a_metric_name(name, q):
    assert stats.name_percentile(name) == q


def test_union_and_idle_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (6.0, 7.0)]
    assert stats.union_length(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert stats.union_length(iv, 1.5, 6.5) == pytest.approx(2.0)
    assert stats.idle_gaps(iv, 0.0, 8.0) == [(2.0, 3.0), (4.0, 6.0),
                                             (7.0, 8.0)]
