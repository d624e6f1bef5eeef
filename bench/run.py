"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <config>.<mix> --seed N --seconds S \
        --trace 0|1

The cell ``<config>.<mix>`` is resolved by name: the sizes from
``bench/configs/<config>.json``, the traffic from
``bench/traffic/<mix>.json``, the correctness limits from
``bench/limits/<cell>.json``, and each metric from
``bench/metrics/<metric>.py`` (or ``<stem>.py``, the name up to its first
dot).  ``BENCHMARK.json`` says which metrics the cell reports.

The configuration's ``reference`` names its architecture's module,
``bench/reference/<reference>.py``, the whole account of that
architecture in the benchmark: ``sizes(conf)`` (the shape numbers the
counts read), ``make_weights(conf, seed)`` (the weights, on the device,
in the layout the program reads), ``decode_step_flops(sizes, lens)`` and
``decode_step_bytes(sizes, lens, kv_bytes)`` (the least work of a decode
step, for the readers) and ``score(...)`` (the plain reference).  This
file names no configuration and no architecture, so a configuration of
another architecture comes with its own files alone.

The harness is a client of the public serving engine
(``repro.serve.ServeEngine``: ``submit``, ``step``, ``status``,
``results``).  Set-up makes the weights from the seed on the device,
builds the engine and warms every prefill length the traffic can send;
``setup_s`` runs from process start to the first request of the traffic.
Then the window: ``--seconds`` of open-loop arrivals, or of a backlog
queued beforehand.  A token counts as delivered when the ``step()`` call
that produced it returns.  After the window the engine is freed and the
plain reference (``bench/reference``) scores a sample of the finished
requests, drawn from the seed with the longest among them.

Standard error carries the diagnostics (device, dispatch resolutions,
compilations in the window, generator lateness, the loop iteration that
ran longest and the engine spans in it, peak memory, medians)
and, as its last lines, each compared number beside its limit.  The last
line of standard output is one JSON object.  Without a TPU, or on a
device missing from ``bench/peaks.json``, it exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
_SRC = os.path.join(ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_cpu_max_isa" not in _flags:        # exact EFTs on the CPU backend
    os.environ["XLA_FLAGS"] = ("--xla_cpu_max_isa=SSE4_2 " + _flags).strip()

import numpy as np  # noqa: E402

import stats  # noqa: E402
import traffic as traffic_mod  # noqa: E402


class DeviceError(RuntimeError):
    """No accelerator this cell can be measured on."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- resolving a cell by name --------------------------------------------------

def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def resolve(bench: Dict, cell: str) -> Dict:
    """The workload entry, configuration, mix and limits of ``cell``."""
    wl = {w["name"]: w for w in bench["workloads"]}
    if cell not in wl:
        raise SystemExit(f"unknown workload {cell!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    w = wl[cell]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return {"workload": w,
            "config": load_json(os.path.join(ROOT, conf["file"])),
            "mix": traffic_mod.load_mix(w["traffic"]),
            "limits": load_json(os.path.join(BENCH, "limits",
                                             f"{cell}.json"))}


def cell_metrics(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, else
    ``<stem>.py`` (the name up to its first dot), else the name without
    its ``_pNN`` part (``ttft_p90_ms`` is read by ``ttft_ms.py``)."""
    for stem in (name, name.split(".")[0], re.sub(r"_p\d+(?=_)", "", name)):
        path = os.path.join(BENCH, "metrics", f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"bench/metrics/")


# -- the device ----------------------------------------------------------------

def check_device(chips: int, peaks_path: str = os.path.join(
        BENCH, "peaks.json")) -> Dict:
    """The devices and their peaks, or :class:`DeviceError`."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise DeviceError(f"JAX found no devices: {e}")
    d = devs[0]
    if d.platform != "tpu":
        raise DeviceError(f"needs a TPU; JAX found {d.platform!r} "
                          f"({d.device_kind})")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips; JAX found "
                          f"{len(devs)}")
    table = load_json(peaks_path)["devices"]
    if d.device_kind not in table:
        raise DeviceError(f"device kind {d.device_kind!r} is not in "
                          f"bench/peaks.json ({sorted(table)})")
    return {"devices": devs, "peaks": table[d.device_kind]}


def enable_compile_cache() -> Optional[str]:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache``), keeping every program, so
    that every run after the first loads all of them."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts tracing and compilation events, and their seconds, while
    ``on`` is set.  A backend compile event also marks a program loaded
    from the persistent cache; ``hits`` counts those loads."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._hear)
        jax.monitoring.register_event_listener(self._count)

    def _hear(self, name, secs, **_kw):
        if self.on and name in self.counts:
            self.counts[name] += 1
            self.secs[name] += secs

    def _count(self, name, **_kw):
        if self.on and name == self.HIT:
            self.hits += 1

    def reset(self) -> "CompileCounter":
        self.on = False
        self.counts = {e: 0 for e in self.EVENTS}
        self.secs = {e: 0.0 for e in self.EVENTS}
        self.hits = 0
        return self

    def summary(self) -> str:
        return ", ".join(f"{k.rsplit('/', 1)[-1]} {v} ({self.secs[k]:.3f} s)"
                         for k, v in self.counts.items()) + (
            f", persistent-cache hits {self.hits}")


_COUNTER: List[CompileCounter] = []


def compile_counter() -> CompileCounter:
    """The process's one counter (a listener cannot be taken back)."""
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0].reset()


# -- the program ---------------------------------------------------------------

# published keys of a configuration file -> the ModelConfig field each sets
PUBLISHED = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "head_dim": "head_dim", "rope_theta": "rope_theta",
             "rms_norm_eps": "norm_eps", "norm_eps": "norm_eps"}
# ModelConfig fields that say how the program runs, not what model it runs
KNOBS = {"attn_block_q", "attn_block_kv", "loss_chunk", "max_seq_len"}


def model_config(conf: Dict):
    """The program's ``ModelConfig`` at the sizes of the configuration
    file, refusing one whose precision differs from what the file says.
    The top level holds the published model; ``program`` holds what the
    program runs where the two differ (recorded under ``departures``).

    It starts from the architecture's full-size config
    (``repro.configs``).  Each key of :data:`PUBLISHED` that the file has
    sets its field; then ``program.fields`` sets any other field by name
    (expert counts, top-k, latent ranks).  A field ``ModelConfig`` does
    not have is refused, and so is a size (any whole-number field but the
    :data:`KNOBS`) below the full-size config's whose key (the published
    key, or the field's own name) the file's ``reduced`` does not list."""
    from repro.configs import get_config
    prog = conf["program"]
    base = get_config(prog["arch"])
    known = {f.name for f in dataclasses.fields(base)}
    by_key = {k: (field, conf[k]) for k, field in PUBLISHED.items()
              if k in conf}
    if "tie_word_embeddings" in prog:
        by_key["program.tie_word_embeddings"] = (
            "tie_embeddings", prog["tie_word_embeddings"])
    for field, v in prog.get("fields", {}).items():
        if field not in known:
            raise ValueError(f"{prog['arch']}: program.fields sets "
                             f"{field!r}, which ModelConfig does not have")
        by_key[field] = (field, v)
    reduced = set(conf.get("reduced", []))
    for key, (field, v) in by_key.items():
        was = getattr(base, field)
        if (isinstance(v, int) and not isinstance(v, bool)
                and isinstance(was, int) and v < was
                and field not in KNOBS and key not in reduced):
            raise ValueError(f"{prog['arch']}: {key} = {v} cuts {field} "
                             f"from {was}, and reduced does not list "
                             f"{key!r}")
    cfg = dataclasses.replace(base, **{f: v for f, v in by_key.values()})
    for k in ("compute_dtype", "param_dtype"):
        if getattr(cfg, k) != prog[k]:
            raise ValueError(f"{prog['arch']}: the program runs {k} "
                             f"{getattr(cfg, k)}, the file states {prog[k]}")
    return cfg


@dataclasses.dataclass
class Rec:
    """What the client saw of one request."""
    item: Any
    due: float
    submit: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    status: str = "QUEUED"


class Client:
    """Drives ``ServeEngine`` through its public calls and times every
    token: with ``sync_every=1`` each ``step()`` returns one token of
    every row that was running, and a request admitted by the step gets
    its prefill token, plus one decoded token where it was admitted before
    the step's decode.  Those are the head of the FIFO queue that fits
    the rows and pages free when the step began (each request reserves
    the pages of its whole trajectory, prompt plus ``max_new``); a
    request of one token retires at its admission and gives its row and
    pages back at once.  A request admitted after the step's decode, into
    what retirements freed, gets its prefill token only."""

    def __init__(self, eng, engine: Dict):
        import jax
        self.eng = eng
        self.max_batch = engine["max_batch"]
        self.page_size = engine["page_size"]
        self.num_pages = engine.get("num_pages") or self.max_batch * -(
            -engine["max_ctx"] // self.page_size)
        self.clock = time.perf_counter
        self.recs: Dict[int, Rec] = {}
        self.queued: List[int] = []
        self.running: List[int] = []
        self.step_times: List[float] = []
        self.mismatch: List[int] = []
        self.trace_on = False
        self.traced_lens: List[List[int]] = []
        self._ann = jax.profiler.TraceAnnotation

    def submit(self, item, due: float) -> None:
        from repro.serve import Request
        with self._ann("bench.submit"):
            rec = Rec(item=item, due=due)
            self.recs[item.uid] = rec
            rec.submit = self.clock()
            st = self.eng.submit(Request(uid=item.uid, prompt=item.prompt,
                                         max_new=item.max_new))
            if st == "QUEUED":
                self.queued.append(item.uid)
            else:
                rec.status = st

    def _pages(self, uid: int) -> int:
        it = self.recs[uid].item
        return -(-(len(it.prompt) + it.max_new) // self.page_size)

    def _admitted_first(self) -> List[int]:
        """The queued requests the step admits before its decode."""
        rows = self.max_batch - len(self.running)
        pages = self.num_pages - sum(self._pages(u) for u in self.running)
        out = []
        for u in self.queued:
            if rows == 0 or self._pages(u) > pages:
                break
            out.append(u)
            if self.recs[u].item.max_new > 1:
                rows -= 1
                pages -= self._pages(u)
        return out

    def step(self) -> bool:
        head = [u for u in self._admitted_first()
                if self.recs[u].item.max_new > 1]
        first = set(head)
        before = list(self.running)
        lens = [len(self.recs[u].item.prompt) + len(self.recs[u].times)
                for u in before] + [len(self.recs[u].item.prompt) + 1
                                    for u in head]
        n0 = self.eng.decode_steps
        with self._ann("bench.step"):
            more = self.eng.step()
        t = self.clock()
        with self._ann("bench.status"):
            decoded = self.eng.decode_steps > n0
            if decoded and self.trace_on:
                self.traced_lens.append(lens)
            self.step_times.append(t)
            still = []
            for u in before:
                self.recs[u].times.append(t)
                if not self._settle(u):
                    still.append(u)
            admitted = 0
            for u in self.queued:
                st = self.eng.status(u)
                if st == "QUEUED":
                    break
                admitted += 1
                self.recs[u].times += [t] * (2 if u in first else 1)
                if not self._settle(u, st):
                    still.append(u)
            self.queued = self.queued[admitted:]
            self.running = still
        return more

    def _settle(self, uid: int, st: Optional[str] = None) -> bool:
        """True when ``uid`` has reached a terminal status."""
        st = st or self.eng.status(uid)
        if st in ("QUEUED", "RUNNING"):
            return False
        rec = self.recs[uid]
        rec.status = st
        if len(self.eng.results[uid].tokens) != len(rec.times):
            self.mismatch.append(uid)
        return True


def warm_up(eng, mix: Dict, vocab: int, rng: np.random.Generator) -> None:
    """Serve one request at every prompt length the mix can send, so that
    each prefill length, the decode step and scoring are compiled and
    loaded before the window."""
    from repro.serve import Request
    for i, S in enumerate(traffic_mod.prompt_lengths(mix)):
        eng.submit(Request(uid=-1 - i, prompt=rng.integers(
            1, vocab, size=S).astype(np.int32), max_new=3))
    while eng.step():
        pass


def profile_options():
    """Device and host events (the engine's and the harness's
    annotations) without the Python tracer, which would slow the host in
    the traced stretch and fill the trace with every Python call."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def drive(client: Client, items, mix: Dict, seconds: float, trace_s: float,
          counter: CompileCounter, trace_dir: Optional[str]) -> Dict:
    """Run the traffic; returns the window's bounds on the client clock."""
    import jax
    clock = client.clock
    kind = mix["kind"]
    pending = list(items)
    if kind == "backlog":
        t_traffic = clock()
        for it in pending:
            client.submit(it, t_traffic)
        pending = []
        while len(client.running) < client.max_batch and client.step():
            pass
        t_open = clock()
    else:
        t_traffic = clock()
        t_open = t_traffic + mix["arrival"]["preroll_s"]
    t_close = t_open + seconds
    for it in pending:
        client.recs[it.uid] = Rec(item=it, due=t_open + it.arrival_s)
    win = None
    busy = kind == "backlog"
    # the loop iteration in the window that ran longest beyond the sleep
    # it asked for: that excess, its parts (loop top, submit, step, wait)
    # and when it began
    slowest = (0.0, 0.0, 0.0, 0.0, 0.0, t_open)
    t_end = clock()
    counter.on = True
    while True:
        t_top, now = t_end, clock()
        if now >= t_close:
            break
        if trace_dir and win is None and now >= t_close - trace_s:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options())
            win = jax.profiler.TraceAnnotation("bench.window")
            win.__enter__()
            client.trace_on = True
        while pending and client.recs[pending[0].uid].due <= now:
            it = pending.pop(0)
            client.submit(it, client.recs[it.uid].due)
            busy = True
        t_sub, stepped, asked = clock(), busy, 0.0
        if busy:
            busy = client.step()
        else:
            nxt = client.recs[pending[0].uid].due if pending else t_close
            with client._ann("bench.wait"):
                asked = max(0.0, min(nxt, t_close) - clock())
                time.sleep(asked)
        t_end = clock()
        if t_top >= t_open and t_end - t_top - asked > slowest[0]:
            ran = t_end - t_sub
            slowest = (t_end - t_top - asked, now - t_top, t_sub - now,
                       ran if stepped else 0.0, 0.0 if stepped else ran,
                       t_top)
    counter.on = False
    if win is not None:
        client.trace_on = False
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return {"traffic": t_traffic, "open": t_open, "close": t_close,
            "slowest": slowest}


# -- correctness -----------------------------------------------------------------

def sample_finished(client: Client, k: int, seed: int) -> List[Dict]:
    """Up to ``k`` finished requests: the longest, and the rest drawn
    from the seed."""
    eng = client.eng
    done = [u for u, r in client.recs.items()
            if r.status == "OK" and u >= 0]
    if not done:
        return []
    total = {u: len(client.recs[u].item.prompt)
             + len(eng.results[u].tokens) for u in done}
    longest = max(done, key=lambda u: (total[u], -u))
    rest = sorted(u for u in done if u != longest)
    rng = np.random.default_rng([seed, 7])
    pick = [longest] + list(rng.choice(rest, size=min(k - 1, len(rest)),
                                       replace=False)) if rest else [longest]
    out = []
    for u in pick:
        r = eng.results[int(u)]
        out.append({"uid": int(u), "prompt": client.recs[int(u)].item.prompt,
                    "tokens": np.asarray(r.tokens, np.int64),
                    "lp": np.asarray(r.logprobs, np.float64),
                    "lp_ff": np.asarray(r.logprobs_ff, np.float64).sum(-1)})
    return out


def compare(ref, conf: Dict, seed: int, sample: List[Dict], mix: Dict,
            control: bool = False) -> Dict:
    """The compared numbers (largest over the sample) of the program
    against the reference module ``ref``, and with ``control`` the same
    numbers of the fp8 control.  Makes the weights again from the seed."""
    w = ref.make_weights(conf, seed)
    T = mix["engine"]["max_ctx"]
    n_pad = mix["output_len"]["max"]
    out = {"logit_gap": 0.0, "logprob_err": 0.0, "logprob_ff_err": 0.0,
           "tokens": 0}
    ctl = {"logit_gap": 0.0, "logprob_err": 0.0, "logprob_ff_err": 0.0,
           "tokens": 0}
    for s in sample:
        S, n = len(s["prompt"]), len(s["tokens"])
        seq = np.zeros((T,), np.int32)
        seq[:S] = s["prompt"]
        seq[S:S + n - 1] = s["tokens"][:-1]
        tg = np.zeros((2, n_pad), np.int32)
        tg[0, :n] = s["tokens"]
        if control:
            c = ref.score(w, conf, seq, S - 1, tg[:1], mode="fp8")
            c = {k: np.asarray(v, np.float64) for k, v in c.items()}
            tg[1] = c["top"].astype(np.int32)
        r = ref.score(w, conf, seq, S - 1, tg, mode="f32")
        r = {k: np.asarray(v, np.float64) for k, v in r.items()}
        lp_ref = (r["at"][0] - r["lse"])[:n]
        out["logit_gap"] = max(out["logit_gap"], float(np.max(
            (r["max"] - r["at"][0])[:n])))
        out["logprob_err"] = max(out["logprob_err"], float(np.max(
            np.abs(s["lp"] - lp_ref))))
        out["logprob_ff_err"] = max(out["logprob_ff_err"], float(np.max(
            np.abs(s["lp_ff"] - lp_ref))))
        out["tokens"] += n
        if control:
            ctl["logit_gap"] = max(ctl["logit_gap"], float(np.max(
                (r["max"] - r["at"][1])[:n])))
            lp_c = (c["max"] - c["lse"])[:n]
            ctl["logprob_err"] = max(ctl["logprob_err"], float(np.max(
                np.abs(lp_c - (r["at"][1] - r["lse"])[:n]))))
            # the control has one scoring tier: its logprob stands for both
            ctl["logprob_ff_err"] = ctl["logprob_err"]
            ctl["tokens"] += n
    del w
    if control:
        out["control"] = ctl
    return out


def _reference(name: str):
    """The architecture's module ``bench/reference/<name>.py``."""
    path = os.path.join(BENCH, "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the numbers a cell may compare; a cell compares those its limits file
# holds, at least one
CHECKED = ("logit_gap", "logprob_err", "logprob_ff_err")


# -- one run -------------------------------------------------------------------

def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             bench: Optional[Dict] = None, parts: Optional[Dict] = None,
             need_chip: bool = True, control: bool = False,
             check: bool = True) -> Dict:
    """One run of ``cell``: the result object, or :class:`DeviceError`.
    ``parts`` replaces what :func:`resolve` finds (a test's small sizes,
    a sweep's rate); ``need_chip=False`` skips the look for a TPU (tests
    on the CPU); ``check=False`` skips the comparison with the reference
    (a rate sweep, which reports no result line)."""
    bench = bench if bench is not None else load_benchmark()
    parts = parts if parts is not None else resolve(bench, cell)
    conf, mix, limits = parts["config"], parts["mix"], parts["limits"]
    if not any(k in limits for k in CHECKED):
        raise ValueError(f"{cell}: its limits compare none of {CHECKED}")
    chips = parts.get("workload", {}).get("chips", 1)
    dev = check_device(chips) if need_chip else None
    import jax
    cache_dir = enable_compile_cache()
    counter = compile_counter()
    import repro.ff as ff
    from repro import obs
    from repro.serve import ServeEngine
    arch = _reference(conf["reference"])

    d0 = jax.devices()[0]
    log(f"device {d0.platform} {d0.device_kind} x{len(jax.devices())}; jax "
        f"{jax.__version__}; compile cache {cache_dir}")
    prog = conf["program"]
    cfg = model_config(conf)
    eng_kw = mix["engine"]
    observer = obs.Observer()
    dispatch0 = obs.REGISTRY.snapshot()["counters"]
    with ff.policy(prog["policy"]), obs.enable():
        t0 = time.perf_counter()
        counter.on = True
        params = arch.make_weights(conf, seed)
        jax.block_until_ready(params)
        t_weights = time.perf_counter()
        eng = ServeEngine(params, cfg, max_batch=eng_kw["max_batch"],
                          page_size=eng_kw["page_size"],
                          max_ctx=eng_kw["max_ctx"],
                          num_pages=eng_kw.get("num_pages"),
                          kv_mode=prog["kv_mode"],
                          guard=prog["guard"],
                          sync_every=eng_kw.get("sync_every", 1),
                          obs=observer)
        del params
        t_engine = time.perf_counter()
        warm_up(eng, mix, cfg.vocab_size, np.random.default_rng(12345))
        log(f"set-up: imports {t0 - T_PROCESS:.3f} s, weights "
            f"{t_weights - t0:.3f} s, engine {t_engine - t_weights:.3f} s, "
            f"warm-up {time.perf_counter() - t_engine:.3f} s; "
            f"{counter.summary()}")
        counter.reset()
        items = traffic_mod.generate(mix, seed, seconds, cfg.vocab_size)
        client = Client(eng, eng_kw)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        t_setup = time.perf_counter()
        obs_open = observer.trace.now()
        win = drive(client, items, mix, seconds, mix.get("trace_s", 3),
                    counter, trace_dir)
    obs_close = observer.trace.now()
    obs_at_setup = obs_open
    obs_open += (win["open"] - t_setup) * 1e6
    setup_s = win["traffic"] - T_PROCESS
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
    dispatch = {k: v - dispatch0.get(k, 0) for k, v in
                obs.REGISTRY.snapshot()["counters"].items()
                if k.startswith("ff_dispatch_resolutions_total")
                and v - dispatch0.get(k, 0) > 0}
    events = observer.trace.events()

    recs = [r for u, r in client.recs.items() if u >= 0]
    due_in = [r for r in recs if win["open"] <= r.due < win["close"]]
    if mix["kind"] == "backlog":
        due_in = [r for r in recs if r.times and r.times[0] < win["close"]]
    attempted = len(due_in)
    failed = sum(1 for r in due_in if r.status not in ("OK", "QUEUED",
                                                       "RUNNING"))
    sample = sample_finished(client, mix["check_requests"], seed)
    n_steps = stats.count_in_window(client.step_times, win["open"],
                                    win["close"])
    run = {
        "cell": cell, "seed": seed, "seconds": seconds, "config": conf,
        "mix": mix, "sizes": arch.sizes(conf),
        "peaks": dev["peaks"] if dev else None,
        "kv_bytes": 2 if prog["kv_mode"] == "bf16" else 4,
        "setup_s": setup_s, "window": win, "recs": recs,
        "traced_lens": client.traced_lens,
        "engine_events": events, "obs_window": (obs_open, obs_close),
        "trace": None, "arch": arch,
    }
    lines = [f"setup {setup_s:.3f} s; window {seconds} s; steps in window "
             f"{n_steps}; requests attempted {attempted}, failed {failed}"]
    lines += [f"dispatch {k} = {v}" for k, v in sorted(dispatch.items())]
    lines.append(f"compilations in the window: {counter.summary()}")
    late = [r.submit - r.due for r in due_in if r.submit is not None]
    p99 = stats.percentile(late, 99)
    lines.append(f"generator lateness p99 "
                 f"{(p99 or 0.0) * 1e3:.3f} ms over {len(late)} submits")
    lines.append(slowest_line(win, events,
                              obs_at_setup + (win["slowest"][-1] - t_setup)
                              * 1e6))
    limit = (d0.memory_stats() or {}).get("bytes_limit")
    lines.append(f"peak HBM after the window {peak} bytes (limit {limit}; "
                 f"compiled programs' scratch not counted)")
    ttft = stats.ttfts([r.due for r in due_in],
                       [r.times[0] if r.times else None for r in due_in],
                       win["close"])
    itl = [g for r in recs
           for g in stats.gaps_in_window(r.times, win["open"],
                                         win["close"])]
    lines.append(f"median ttft {(stats.percentile(ttft, 50) or 0) * 1e3:.3f}"
                 f" ms, p90 {(stats.percentile(ttft, 90) or 0) * 1e3:.3f} ms "
                 f"over {len(ttft)}; median itl "
                 f"{(stats.percentile(itl, 50) or 0) * 1e3:.3f} ms over "
                 f"{len(itl)}; delivery mismatches {len(client.mismatch)}")

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        import trace_reduce
        path = _xplane(trace_dir)
        run["trace"] = trace_reduce.reduce_trace(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = run["trace"]
        if red and red["window"]:
            lo, hi = red["window"]["start"], red["window"]["end"]
            ndev = max(1, len(red["devices"]))
            busy = sum(stats.union_length(
                [(o["start"], o["end"]) for o in red["ops"]
                 if o["device"] == dv], lo, hi) for dv in red["devices"])
            device["busy_s"] = busy / ndev
            device["window_s"] = hi - lo
            breakdown = _breakdown(red)
            lines.append(f"trace: {len(red['modules'])} programs, "
                         f"{len(red['ops'])} device ops, "
                         f"{len(trace_reduce.decode_modules(red))} decode "
                         f"steps on the device, "
                         f"{len(client.traced_lens)} decode calls traced")
    metrics = {}
    for m in cell_metrics(bench, cell, trace):
        v = load_reader(m["name"])(run, m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            lines.append(f"METRIC MISSING: {m['name']} found nothing to "
                         f"read in this run")
    for ln in lines:
        log(ln)

    depth = [e["args"]["depth"] for e in events
             if e.get("ph") == "C" and e["name"] == "queue"
             and obs_open <= e["ts"] < obs_close]
    del eng, client, run
    gc.collect()
    if not check:
        third = max(1, len(depth) // 3)
        return {"metrics": metrics, "attempted": attempted,
                "queue_first_third": float(np.mean(depth[:third] or [0])),
                "queue_last_third": float(np.mean(depth[-third:] or [0]))}
    cmp = compare(arch, conf, seed, sample, mix, control=control)
    compared = [k for k in CHECKED if k in limits]
    checks = {k: {"value": cmp[k], "limit": limits[k]} for k in compared}
    ok = (bool(sample) and failed == 0
          and all(c["value"] <= c["limit"] for c in checks.values()))
    log(f"compared {len(sample)} finished requests, {cmp['tokens']} served "
        f"tokens, against the float32 reference")
    for k in CHECKED:
        if k not in limits:
            log(f"reading {k} = {cmp[k]!r} (not compared in this cell)")
    for k, c in checks.items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        ctl = {k: {"value": cmp["control"][k], "limit": limits[k]}
               for k in compared}
        result["control"] = {
            "correct": all(c["value"] <= c["limit"] for c in ctl.values()),
            "tokens": cmp["control"]["tokens"], "checks": ctl,
            "readings": {k: cmp["control"][k] for k in CHECKED},
            "program": {k: cmp[k] for k in CHECKED}}
        for k, c in ctl.items():
            log(f"control {k} = {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    return result


def slowest_line(win: Dict, events: List[Dict], obs_at: float) -> str:
    """The window's loop iteration that ran longest beyond the sleep it
    asked for, its parts, and the engine spans that ran in it (``obs_at``:
    its start on the recorder's clock)."""
    over, top, sub, step, wait, at = win["slowest"]
    lo, hi = obs_at, obs_at + (top + sub + step + wait) * 1e6
    inside: Dict[str, float] = {}
    for e in events:
        if e.get("ph") == "X" and e["name"].startswith("serve."):
            o = min(hi, e["ts"] + e["dur"]) - max(lo, e["ts"])
            if o > 0:
                inside[e["name"]] = inside.get(e["name"], 0.0) + o
    spans = ", ".join(f"{k} {v / 1e3:.3f} ms" for k, v in sorted(
        inside.items(), key=lambda kv: -kv[1])[:6])
    return (f"slowest loop iteration in the window: {over * 1e3:.3f} ms "
            f"beyond its asked sleep, at "
            f"+{at - win['open']:.3f} s: loop top {top * 1e3:.3f} ms, "
            f"submit {sub * 1e3:.3f} ms, step {step * 1e3:.3f} ms, wait "
            f"{wait * 1e3:.3f} ms; engine spans in it: {spans or 'none'}")


def _xplane(directory: Optional[str]) -> Optional[str]:
    import glob
    if not directory:
        return None
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _op_name(hlo: str) -> str:
    """A device op's name, result type and opcode, from the HLO text the
    trace names it by: ``%convert.29 = bf16[12,9216,3072]{2,1,0:T(8,128)}
    convert(...)`` becomes ``%convert.29 = bf16[12,9216,3072] convert``."""
    lhs, eq, rhs = hlo.partition(" = ")
    m = re.match(r"(.*?) ([A-Za-z][\w-]*)\(", re.sub(r"\{[^{}]*\}", "",
                                                       rhs))
    if not eq or not m:
        return hlo[:120]
    return f"{lhs} = {m.group(1)} {m.group(2)}"[:120]


def _breakdown(red: Dict) -> Dict:
    """The device ops that took most time, and the idle gaps by the host
    span that was open in them (innermost), both within the window."""
    lo, hi = red["window"]["start"], red["window"]["end"]
    by_op: Dict[str, float] = {}
    for o in red["ops"]:
        if lo <= o["start"] < hi:
            name = _op_name(o["name"])
            by_op[name] = by_op.get(name, 0.0) + (o["end"] - o["start"])
    dev0 = red["devices"][0] if red["devices"] else None
    gaps = stats.idle_gaps([(o["start"], o["end"]) for o in red["ops"]
                            if o["device"] == dev0], lo, hi)
    spans = [h for h in red["host"] if h["name"] != "bench.window"]
    by_host: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        open_ = [h for h in spans if h["start"] <= mid < h["end"]]
        name = (max(open_, key=lambda h: h["start"])["name"] if open_
                else "untraced host")
        by_host[name] = by_host.get(name, 0.0) + (b - a)

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:10]
    return {"device_ops": top(by_op), "idle_gaps": top(by_host)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except DeviceError as e:
        log(f"no result: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
