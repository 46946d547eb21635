"""The benchmark's harness: finds a cell's files by name, builds the engine,
warms every shape the cell's traffic can reach, drives the measured window,
reduces what it saw to metrics, and decides ``correct``.

Everything that belongs to one configuration, traffic mix or metric lives in
a file of its own, found by the name ``BENCHMARK.json`` gives:

* configuration ``<c>``: the file its ``configs`` entry names; its
  ``reference`` key names ``bench/references/<reference>.py``;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* metric ``<base>.<suffix>`` or ``<base>``: ``bench/metrics/<base>.py``,
  whose ``read(run)`` returns a number, or None where it finds nothing.

The engine is the program's ``serve.Engine``, read only through ``submit``,
``run``, ``stats()``, ``step_log``, ``decode_wall_s()`` and ``wall_spans``.
``Engine.run`` drains its whole queue and admits nothing while it runs, so
the client submits what is due and then calls ``run``: an offline mix
submits a wave at a time until ``seconds`` have passed; an open-loop mix
submits every request that has come due, and sleeps when none has.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import inspect
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench import traffic as gen
from bench.stats import percentile

ROOT = Path(__file__).resolve().parents[1]
# Host spans the harness writes into a trace; idle gaps are named by them.
SPANS = ("engine.run", "client.wait_arrival")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path

    @property
    def sizes(self) -> Dict:
        return self.config["model"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its configuration
    and traffic files read.  Raises KeyError for an unknown name."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    gen.check_fits(mix, config["serve"]["max_len"])
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic=mix,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"],
                root=root)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(cell: Cell, metric: str) -> Callable:
    base = metric.split(".")[0]
    return _load_module(cell.root / "bench" / "metrics" / f"{base}.py",
                        f"bench_metric_{base}").read


def reference(cell: Cell):
    ref = cell.config["reference"]
    return _load_module(cell.root / "bench" / "references" / f"{ref}.py",
                        f"bench_reference_{ref}")


def _applies(entry: Dict, cell: Cell, reported: Optional[set] = None) -> bool:
    if "workloads" in entry:
        return cell.name in entry["workloads"]
    return reported is None or entry["moves"] in reported


def metrics_for(cell: Cell, trace: bool) -> List[Dict]:
    """The metric entries this cell reports: its end-to-end metrics, or
    with ``trace`` its per-layer metrics."""
    e2e = [m for m in cell.end_to_end if _applies(m, cell)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in cell.per_layer if _applies(m, cell, names)]


# ---------------------------------------------------------------------------
# What one run saw
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read.  Times are seconds on the
    host's ``perf_counter`` clock, relative to the window's start."""

    sizes: Dict
    setup_s: float
    window_s: float
    requests: List[Dict]          # due, admit, first_token, finish, ...
    steps: List[Dict]             # admitted, kv_lens, pure_decode
    decode_wall_s: float
    decode_batches: int
    compiles: int                 # compiles and cache loads in the window
    memory_peak_bytes: Optional[int]
    peaks: Optional[Dict]         # bench.peaks entry; None off the chip
    trace: Optional[Dict] = None  # bench.trace_reduce result


class Client:
    """Submits requests, drives ``Engine.run`` and keeps what it saw."""

    def __init__(self, eng, request_cls):
        self.eng = eng
        self.request_cls = request_cls
        self.clock = time.perf_counter   # the engine's own span clock
        self.t0 = 0.0
        self.requests: List[Dict] = []
        self.steps: List[Dict] = []
        self.decode_wall_s = 0.0
        self.decode_batches = 0
        self._rid = 0

    def serve(self, batch) -> None:
        """Submit ``batch`` (pairs of a ``traffic.Req`` and the host time it
        was due) and run the engine until all of it is done."""
        live = []
        for req, due in batch:
            r = self.request_cls(rid=self._rid, prompt=req.prompt,
                                 max_new_tokens=req.max_new_tokens)
            self._rid += 1
            self.eng.submit(r)
            live.append((r, due, self.clock()))
        with jax.profiler.TraceAnnotation("engine.run"):
            self.eng.run()
        spans = {s.rid: s for s in self.eng.wall_spans}
        for r, due, submit in live:
            s = spans.get(r.rid)
            self.requests.append({
                "prompt": r.prompt, "out": list(r.out_tokens or ()),
                "max_new": r.max_new_tokens, "prompt_len": len(r.prompt),
                "due": due - self.t0, "submit": submit - self.t0,
                "admit": s.admit - self.t0 if s else None,
                "first_token": s.first_token - self.t0 if s else None,
                "finish": s.finish - self.t0 if s else None})
        for rec in self.eng.step_log:
            self.steps.append({
                "admitted": len(rec.admitted),
                "kv_lens": tuple(int(k) for k in rec.kv_lens),
                "pure_decode": bool(rec.decoded) and not rec.admitted})
        self.decode_wall_s += self.eng.decode_wall_s()
        self.decode_batches += int(self.eng.stats()["decode_batches"])


def drive(client: Client, cell: Cell, seed: int, seconds: float) -> float:
    """The measured window; returns its length in seconds."""
    mix, vocab = cell.traffic, cell.sizes["vocab_size"]
    slots = cell.config["serve"]["slots"]
    clock = client.clock
    client.t0 = t0 = clock()
    if mix["kind"] == "offline":
        w = 0
        while True:
            now = clock()
            client.serve([(r, now) for r in
                          gen.wave(mix, seed, w, slots, vocab)])
            w += 1
            if clock() - t0 >= seconds:
                break
    elif mix["kind"] == "open_loop":
        deck = gen.open_loop(mix, seed, seconds, vocab)
        i = 0
        while i < len(deck):
            now = clock()
            due = []
            while i < len(deck) and t0 + deck[i].due_s <= now:
                due.append((deck[i], t0 + deck[i].due_s))
                i += 1
            if due:
                client.serve(due)
            else:
                with jax.profiler.TraceAnnotation("client.wait_arrival"):
                    time.sleep(max(0.0, t0 + deck[i].due_s - now))
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return clock() - t0


# ---------------------------------------------------------------------------
# Set-up: weights, engine, warm-up
# ---------------------------------------------------------------------------

def model_config(cell: Cell):
    from repro.core.types import Family, ModelConfig
    m = dict(cell.sizes)
    m["family"] = Family(m["family"])
    return ModelConfig(name=cell.config_name, **m)


def seed_key(seed: int, stream: int):
    """A JAX key for ``stream`` of ``seed``; any whole number works."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(state, impl="threefry2x32")


def make_params(cell: Cell, cfg, seed: int):
    """The reference's seeded weights, checked against the layout, shapes
    and dtypes the program's own ``init`` would give."""
    from repro.configs import registry
    params = reference(cell).make_params(cell.sizes, seed_key(seed, 0))
    want = registry.param_specs(cfg)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise ValueError(f"weights do not match the program's layout:\n"
                         f"{got}\nvs\n{exp}")
    return params


def _pages(n: int, page: int) -> int:
    return -(-n // page)


def warm_pool(cfg, slots: int, max_len: int, page_size: int, mix: Dict):
    """Run the K/V pool's gather and scatter once for every bucket size and
    page count the mix can reach, so that none compiles in the window."""
    import jax.numpy as jnp
    from repro.serve.kv_cache import PagedKVCache
    sup = gen.support(mix)
    lo = _pages(sup["min_prompt"], page_size)
    hi = _pages(min(max_len, sup["max_prompt"] + sup["max_new"] - 1),
                page_size)
    L, hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    pool = PagedKVCache(slots=slots, num_layers=L, kv_heads=hkv,
                        width=max_len, head_dim=hd, dtype=dt,
                        page_size=page_size)
    empty = {"layers": {"k": jnp.zeros((L, 1, hkv, 1, hd), dt),
                        "v": jnp.zeros((L, 1, hkv, 1, hd), dt)},
             "len": jnp.asarray(0, jnp.int32)}
    for b in range(1, slots + 1):
        ids = list(range(b))
        dense = jnp.zeros((L, b, hkv, max_len, hd), dt)
        for s in ids:
            pool.admit(s, empty)
        for npg in range(lo, hi + 1):
            n = min(npg * pool.page_size, max_len)
            pool.scatter(ids, {"layers": {"k": dense, "v": dense},
                               "len": jnp.asarray(n, jnp.int32)})
            jax.block_until_ready(pool.gather(ids))
        for s in ids:
            pool.free(s)
        del dense
    del pool


def warm_open_loop(client: Client, cell: Cell, seed: int) -> None:
    """For an open loop, whose arrivals decide the shapes: each grid length
    once through prefill, then decode at every bucket size from 1 to
    ``slots`` (the pool's shapes are ``warm_pool``'s)."""
    sup = gen.support(cell.traffic)
    rng = np.random.default_rng([int(seed), 3])
    vocab = cell.sizes["vocab_size"]

    def req(n):
        return gen.Req(rng.integers(0, vocab, size=(n,), dtype=np.int32), 2)

    client.serve([(req(g), 0.0) for g in sup["grid"]])
    for b in range(2, cell.config["serve"]["slots"] + 1):
        client.serve([(req(sup["min_prompt"]), 0.0) for _ in range(b)])


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def pick_sample(requests: List[Dict], seed: int, tokens: int,
                max_requests: int) -> List[int]:
    """Indices of finished requests to check: the one with the most
    served tokens, then others in an order drawn from the seed, until
    ``tokens`` served tokens or ``max_requests`` requests."""
    done = [i for i, r in enumerate(requests) if r["out"]]
    if not done:
        return []
    longest = max(done, key=lambda i: (len(requests[i]["out"]),
                                       requests[i]["prompt_len"]))
    rest = [i for i in done if i != longest]
    np.random.default_rng([int(seed), 2]).shuffle(rest)
    picked, n = [], 0
    for i in [longest] + rest:
        if n >= tokens or len(picked) >= max_requests:
            break
        picked.append(i)
        n += len(requests[i]["out"])
    return picked


def served_gaps(cell: Cell, params, requests: List[Dict],
                control: bool = False) -> List[np.ndarray]:
    """Per request, at each served token, the gap by which its logit lies
    below the reference's best logit at that position (with ``control``,
    the gap of the token float8 computation ranks first instead)."""
    import jax.numpy as jnp
    ref = reference(cell)
    sup = gen.support(cell.traffic)
    width = -(-(sup["max_prompt"] + sup["max_new"] - 1) // 128) * 128
    tmax = sup["max_new"]
    out = []
    for r in requests:
        prompt, toks = np.asarray(r["prompt"], np.int32), list(r["out"])
        n, t = len(prompt), len(toks)
        seq = np.zeros((width,), np.int32)
        seq[:n] = prompt
        seq[n:n + t - 1] = toks[:-1]
        pos = np.zeros((tmax,), np.int32)
        pos[:t] = np.arange(n - 1, n - 1 + t)
        tgt = np.zeros((tmax,), np.int32)
        tgt[:t] = toks
        valid = np.arange(tmax) < t
        args = (jnp.asarray(seq), jnp.asarray(pos))
        if control:
            g = ref.control_gaps(cell.sizes, params, *args,
                                 jnp.asarray(valid))
        else:
            g = ref.gaps(cell.sizes, params, *args, jnp.asarray(tgt),
                         jnp.asarray(valid))
        out.append(np.asarray(g, np.float64)[:t])
    return out


def widest(gaps: List[np.ndarray]) -> float:
    """The widest gap; a non-finite logit, or nothing to compare, reads as
    a huge gap (JSON has no infinity)."""
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    if g.size == 0 or not np.all(np.isfinite(g)):
        return 1e30
    return float(g.max())


def gap_summary(gaps: List[np.ndarray]) -> str:
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    top = np.sort(g)[::-1][:5]
    return (f"{g.size} tokens, {int(np.sum(g > 0))} off the reference's "
            f"best, widest {[float(x) for x in top]}")


def check_requests(requests: List[Dict], vocab: int) -> int:
    """Requests that did not come back whole: missing tokens, or a token
    outside the vocabulary."""
    bad = 0
    for r in requests:
        out = r["out"]
        if (len(out) != r["max_new"] or r["finish"] is None
                or any(not 0 <= t < vocab for t in out)):
            bad += 1
    return bad


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def build(cell: Cell, seed: int):
    """Weights, warmed programs and a client around a fresh engine.

    An offline cell serves one wave in set-up: every wave asks for the
    same lengths in the same order, so that wave runs every program, at
    every shape, that the window's waves run."""
    from repro.serve.engine import Engine, Request
    cfg = model_config(cell)
    mix = cell.traffic
    slots = cell.config["serve"]["slots"]
    max_len = cell.config["serve"]["max_len"]
    t0 = time.perf_counter()
    if mix["kind"] != "offline":
        page = inspect.signature(
            Engine.__init__).parameters["page_size"].default
        warm_pool(cfg, slots, max_len, page, mix)
    params = make_params(cell, cfg, seed)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    eng = Engine(cfg, params, slots=slots, max_len=max_len)
    client = Client(eng, Request)
    if mix["kind"] == "offline":
        client.serve([(r, 0.0) for r in gen.wave(
            mix, seed, 0, slots, cell.sizes["vocab_size"], stream=3)])
    else:
        warm_open_loop(client, cell, seed)
    log(f"[bench] set-up: weights {t1 - t0:.3f} s, warm-up "
        f"{time.perf_counter() - t1:.3f} s")
    return params, Client(eng, Request)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: Optional[Dict] = None,
             device=None, control: bool = False,
             keep_trace: Optional[str] = None) -> Dict:
    """One run of ``cell``: the result line's object, without printing.

    With ``control`` the float8 reference takes the program's place in the
    comparison (the program's own reading is kept as ``program_gap``); the
    benchmark's runs never set it.  With ``keep_trace`` a traced run's
    profile is left in that directory."""
    from repro.core.compile_cache import CompileCounter
    device = device or jax.devices()[0]
    params, client = build(cell, seed)
    # Set-up's objects go to the permanent generation, so that the
    # window's collections do not walk them again.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"[bench] {cell.name}: set-up {setup_s:.3f} s")

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with CompileCounter() as cc:
            with jax.profiler.TraceAnnotation("window"):
                window_s = drive(client, cell, seed, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
        gc.unfreeze()
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    summary = None
    if trace:
        from bench import trace_reduce
        try:
            summary = trace_reduce.reduce_trace(
                trace_reduce.find_trace(trace_dir), SPANS)
        finally:
            if not keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)

    run = RunRecord(
        sizes=cell.sizes, setup_s=setup_s, window_s=window_s, requests=client.requests,
        steps=client.steps, decode_wall_s=client.decode_wall_s,
        decode_batches=client.decode_batches, compiles=cc.requests,
        memory_peak_bytes=peak, peaks=peaks, trace=summary)
    log(f"[bench] window {window_s:.3f} s, {len(run.requests)} requests, "
        f"{sum(len(r['out']) for r in run.requests)} tokens, "
        f"{len(run.steps)} steps; compiled {cc.compiled}, "
        f"cache loads {cc.cache_hits} in the window; peak {peak}")
    lateness = [r["submit"] - r["due"] for r in run.requests]
    log(f"[bench] generator lateness p50 {percentile(lateness, 0.5)} s, "
        f"max {max(lateness) if lateness else None} s")

    metrics = {}
    for m in metrics_for(cell, trace):
        value = metric_reader(cell, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # The program's state goes before the reference runs.
    client.eng = None
    del client
    gc.collect()
    failed = check_requests(run.requests, cell.sizes["vocab_size"])
    chk = cell.traffic["check"]
    sample = pick_sample(run.requests, seed, chk["tokens"],
                         chk["max_requests"])
    t_ref = time.perf_counter()
    picked = [run.requests[i] for i in sample]
    gaps = served_gaps(cell, params, picked, control=control)
    gap = widest(gaps)
    limit = float(cell.config["check"]["served_logit_gap"])
    log(f"[bench] reference over {len(sample)} requests, "
        f"{time.perf_counter() - t_ref:.3f} s: {gap_summary(gaps)}")
    checks = {
        "unfinished_requests": {"value": failed, "limit": 0},
        "served_logit_gap": {"value": gap, "limit": limit},
    }
    correct = failed == 0 and gap <= limit
    result = {
        "correct": bool(correct),
        "attempted": len(run.requests),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if control:
        prog = served_gaps(cell, params, picked)
        result["program_gap"] = widest(prog)
        log(f"[bench] control run: the program's {gap_summary(prog)}")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result


def main(args, t_start: float) -> int:
    """The command line's run: nonzero, and no result, without the chips
    the cell asks for."""
    try:
        cell = load_cell(args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        log(f"bench: {e}")
        return 2
    try:
        from repro.core.compile_cache import use_persistent_cache
    except ImportError as e:
        log(f"bench: the program is not importable here: {e}")
        return 1
    use_persistent_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: needs a TPU; JAX found {devices[0].platform!r}")
        return 1
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips; "
            f"{len(devices)} present")
        return 1
    from bench.peaks import peaks
    try:
        pk = peaks(devices[0].device_kind)
    except KeyError as e:
        log(f"bench: {e}")
        return 1
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=t_start, peaks=pk,
                      device=devices[0])
    print(json.dumps(result), flush=True)
    return 0
