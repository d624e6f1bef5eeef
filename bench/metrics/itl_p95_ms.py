"""itl_p95_ms: 95th percentile of the gaps between consecutive returned
tokens of a request, over every gap that ends in the window."""

import stats


def read(run, name):
    w = run["window"]
    gaps = [g for r in run["recs"]
            for g in stats.gaps_in_window(r.times, w["open"], w["close"])]
    v = stats.percentile(gaps, 95)
    return None if v is None else v * 1e3
