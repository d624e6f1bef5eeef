"""ttft_ms.pNN: NN-th percentile of the time from a request's due time to
the return of its first token, over every request due in the window; one
still waiting at the close counts with the time it has waited."""

import stats


def read(run, name):
    q = float(name.split(".p", 1)[1])
    w = run["window"]
    due = [r for r in run["recs"] if w["open"] <= r.due < w["close"]]
    v = stats.percentile(stats.ttfts(
        [r.due for r in due], [r.times[0] if r.times else None for r in due],
        w["close"]), q)
    return None if v is None else v * 1e3
