"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``.

Every seed gets the same work.  Each length distribution and the gaps
between arrivals are sampled at the stratified quantiles
``(i + 0.5) / n`` and only their ORDER (and the prompt token ids) come
from the seed, so two seeds differ in which request comes when, never in
how much there is to do.

Kinds of mix:

* ``open_loop``: independent users, in two stretches drawn alike:
  ``round(rate * preroll_s)`` requests spanning ``[-preroll_s, 0)`` and
  ``round(rate * seconds)`` spanning ``[0, seconds)``, each with
  exponential gaps scaled to span its stretch exactly and lengths from
  its own strata; time 0 is the window's opening.  So the window holds
  the same requests, by number and by size, for every seed.  Where the
  ``arrival`` block names a ``schedule_seed``, the schedule (the order of
  the gaps and of the prompt lengths) is drawn from it and not from the
  run's seed: every run replays one arrival trace, and the run's seed
  draws the order of the answer lengths and the prompt tokens.
* ``backlog``: offline batch work, all queued before the window opens:
  ``max_batch + ceil(backlog_per_s * seconds)`` requests.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
from typing import Dict, List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(HERE, "traffic")


@dataclasses.dataclass
class Item:
    uid: int
    arrival_s: float        # due time, relative to the window's opening
    prompt: np.ndarray      # (S,) int32 token ids
    max_new: int


def load_mix(name: str, directory: str = TRAFFIC_DIR) -> Dict:
    path = os.path.join(directory, f"{name}.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no traffic mix {name!r}: {path} is missing")
    with open(path) as f:
        mix = json.load(f)
    mix.setdefault("name", name)
    return mix


def strata(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def quantile(dist: Dict, u: np.ndarray) -> np.ndarray:
    """Length for each quantile ``u`` in (0, 1) under ``dist``."""
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(float(x)) for x in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "uniform_int":
        lo, hi = dist["min"], dist["max"]
        x = lo + np.floor(u * (hi - lo + 1))
    elif kind == "choice":
        vals = np.asarray(dist["values"], np.float64)
        x = vals[np.minimum((u * len(vals)).astype(int), len(vals) - 1)]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    if "buckets" in dist:
        b = np.asarray(sorted(dist["buckets"]), np.float64)
        idx = np.minimum(np.searchsorted(b, np.ceil(x)), len(b) - 1)
        x = b[idx]
    x = np.rint(x)
    if "min" in dist:
        x = np.maximum(x, dist["min"])
    if "max" in dist:
        x = np.minimum(x, dist["max"])
    return x.astype(np.int64)


def stretches(mix: Dict, seconds: float) -> List[Tuple[float, float, int]]:
    """``(start, span, requests)`` of each stretch of arrivals."""
    if mix["kind"] == "open_loop":
        a = mix["arrival"]
        pre = (-a["preroll_s"], a["preroll_s"],
               int(round(a["rate_per_s"] * a["preroll_s"])))
        win = (0.0, seconds, max(1, int(round(a["rate_per_s"] * seconds))))
        return [s for s in (pre, win) if s[2] > 0]
    if mix["kind"] == "backlog":
        return [(0.0, 0.0, mix["engine"]["max_batch"] + int(
            math.ceil(mix["backlog_per_s"] * seconds)))]
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def request_count(mix: Dict, seconds: float) -> int:
    return sum(n for _, _, n in stretches(mix, seconds))


def arrivals(mix: Dict, n: int, span: float,
             rng: np.random.Generator) -> np.ndarray:
    """Offsets of ``n`` arrivals from the start of a stretch ``span`` long."""
    if mix["kind"] == "backlog":
        return np.zeros((n,), np.float64)
    a = mix["arrival"]
    if a["process"] != "poisson":
        raise ValueError(f"unknown arrival process {a['process']!r}")
    gaps = -np.log1p(-rng.permutation(strata(n)))
    gaps = gaps * (span / gaps.sum())      # the same scale for every seed
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def generate(mix: Dict, seed: int, seconds: float,
             vocab_size: int) -> List[Item]:
    """The cell's requests for ``seed``, in arrival order."""
    rng = np.random.default_rng(seed)
    fixed = mix.get("arrival", {}).get("schedule_seed")
    sched = rng if fixed is None else np.random.default_rng(fixed)
    t, plen, olen = [], [], []
    for start, span, n in stretches(mix, seconds):
        t.append(start + arrivals(mix, n, span, sched))
        plen.append(quantile(mix["prompt_len"],
                             sched.permutation(strata(n))))
        olen.append(quantile(mix["output_len"], rng.permutation(strata(n))))
    t, plen, olen = (np.concatenate(x) for x in (t, plen, olen))
    return [Item(uid=i, arrival_s=float(t[i]),
                 prompt=rng.integers(1, vocab_size, size=int(plen[i]),
                                     dtype=np.int64).astype(np.int32),
                 max_new=int(olen[i]))
            for i in range(len(t))]


def prompt_lengths(mix: Dict) -> List[int]:
    """Every prompt length the mix can send: the shapes set-up warms."""
    d = mix["prompt_len"]
    if "buckets" in d:
        return sorted(int(b) for b in d["buckets"])
    if d["dist"] == "choice":
        return sorted(int(v) for v in d["values"])
    raise ValueError("a mix must fix its prompt lengths (buckets or choice) "
                     "so that set-up can warm every prefill shape")
