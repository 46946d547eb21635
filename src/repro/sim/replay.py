"""``repro.sim.replay`` — record real kernel timings, attach them to
``ExecutionPlan`` layers, replay them through ``simulate_plan``, and fit a
calibration of the analytic timing model (DESIGN.md §10).

The simulator's per-op timing is analytic; the paper's headline claims
rest on *measured* kernel behavior.  Following CIMFlow's
record-then-calibrate loop (arXiv:2505.01107) and NeuroSim's validated
cost tables (arXiv:2505.02314), this module closes the loop in four
steps:

1. **Record** — ``KernelRecorder`` instruments the jnp/Pallas kernel
   paths (``kernels.ops.attention_by_plan``, ``kernels.tile_gemm``,
   ``kernels.stream_attention``): inside a ``recording()`` block each
   executed op emits a ``KernelTrace`` (grid shape, block tiling actually
   used, wall-time- or cost-analysis-derived cycles, bytes moved).
   ``record_plan`` drives a whole plan's op list through the kernels at
   the plan's own geometry.
2. **Attach** — ``ExecutionPlan.attach_traces`` matches records to
   ``LayerPlan``/``GemmPlan`` entries by op name; traces serialize with
   the plan (``to_json``/``from_json`` round-trip them exactly).
3. **Replay** — ``simulate_plan`` lowers a traced op to its *recorded*
   timing (one compute-resource event spanning ``trace.cycles`` plus an
   HBM accounting event carrying ``trace.hbm_bytes``) instead of the
   analytic task graph; untraced ops fall back to analytic lowering, so
   mixed plans simulate end-to-end.
4. **Calibrate** — ``fit_calibration`` quantifies analytic-vs-recorded
   error per op class and fits a per-resource cycle scale factor
   (ridge-regularized least squares over the analytic per-op busy-cycle
   decomposition).  ``simulate_plan(plan, calibration=report)`` and the
   DSE sweep (``run_sweep(calibrations=...)``) apply it to analytic
   lowering.

Wall-clock seconds convert to cycles at ``KernelRecorder.clock_hz``
(default 1 GHz — the napkin CIM clock).  On CPU-hosted runs the recorded
cycles are *host-platform* timings, so absolute calibration factors are
large and only meaningful per platform; the pipeline, not the constants,
is the contract (DESIGN.md §10 discusses when replayed timing diverges).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import time
from typing import (Callable, Dict, Iterable, Iterator, List, Mapping,
                    Optional, Sequence, Tuple, Union)

KERNEL_TRACE_VERSION = 1

#: Napkin CIM clock for wall-seconds -> cycles conversion (unclocked
#: simulator; ratios between records on one platform are what matter).
DEFAULT_CLOCK_HZ = 1e9

#: Op classes a ``KernelTrace`` can describe; the replay lowering charges
#: the recorded cycles to the class's primary macro-array resource.
TRACE_KINDS = ("attention", "gemm", "decode")
_KIND_RESOURCE = {"attention": "ATTN", "gemm": "GEN", "decode": "ATTN"}


# ---------------------------------------------------------------------------
# KernelTrace
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelTrace:
    """One recorded kernel execution (the unit the replay lowering eats).

    ``op`` names the plan op the record belongs to (``LayerPlan.name`` /
    ``GemmPlan.name``); kernel-level sub-records use ``parent/kernel``
    labels and never attach to a plan.  ``cycles`` is the recorded op
    duration in CIM clock cycles (wall seconds x ``clock_hz``, or an XLA
    cost-analysis estimate — see ``source``); ``hbm_bytes`` the bytes the
    executed arrays actually moved.
    """

    op: str
    kind: str                  # "attention" | "gemm"
    mode: str                  # ExecutionMode value ("" for bare kernels)
    grid: Tuple[int, ...]      # kernel grid actually launched
    block_q: int               # q-tile edge actually used (gemm: block_m)
    block_kv: int              # kv-tile edge actually used (gemm: block_n)
    cycles: int                # recorded duration, CIM clock cycles
    hbm_bytes: int             # bytes moved by the executed arrays
    wall_time_s: float = 0.0   # measured wall seconds (0 for cost_analysis)
    flops: int = 0
    clock_hz: float = DEFAULT_CLOCK_HZ
    source: str = "wall_time"  # "wall_time" | "cost_analysis" | "manual"

    def __post_init__(self):
        if self.kind not in TRACE_KINDS:
            raise ValueError(f"{self.op}: kind must be one of "
                             f"{TRACE_KINDS}, got {self.kind!r}")
        if self.cycles <= 0:
            raise ValueError(f"{self.op}: recorded cycles must be > 0, "
                             f"got {self.cycles!r}")
        if self.hbm_bytes < 0:
            raise ValueError(f"{self.op}: hbm_bytes must be >= 0, "
                             f"got {self.hbm_bytes!r}")

    @property
    def resource(self) -> str:
        """The macro-array resource replay charges the cycles to."""
        return _KIND_RESOURCE[self.kind]

    def to_dict(self) -> Dict[str, object]:
        d = dataclasses.asdict(self)
        d["version"] = KERNEL_TRACE_VERSION
        d["grid"] = list(self.grid)
        return d

    @classmethod
    def from_dict(cls, d: Mapping[str, object]) -> "KernelTrace":
        d = dict(d)
        version = d.pop("version", KERNEL_TRACE_VERSION)
        if version != KERNEL_TRACE_VERSION:
            raise ValueError(f"unsupported KernelTrace version {version!r}")
        d["grid"] = tuple(int(g) for g in d.get("grid", ()))
        return cls(**d)


# ---------------------------------------------------------------------------
# Recorder + active-recorder registry (the kernel instrumentation hook)
# ---------------------------------------------------------------------------

class KernelRecorder:
    """Collects ``KernelTrace`` records from instrumented kernel paths.

    The instrumented entry points (``ops.attention_by_plan``,
    ``tile_gemm``, ``stream_attention``) consult ``active_recorder()``:
    inside a ``recording(rec)`` block every concrete (non-traced) call
    appends a record.  ``measure`` times a thunk with warmup and median-
    of-iters (mirroring ``benchmarks.common.time_fn``) and suppresses
    nested kernel-level records so one op yields one op-level trace.
    """

    def __init__(self, clock_hz: float = DEFAULT_CLOCK_HZ, *,
                 iters: int = 1, warmup: int = 1) -> None:
        if clock_hz <= 0:
            raise ValueError(f"clock_hz must be > 0, got {clock_hz!r}")
        self.clock_hz = clock_hz
        self.iters = max(1, iters)
        self.warmup = max(0, warmup)
        self.records: List[KernelTrace] = []
        self._labels: List[str] = []
        self._suppressed = 0

    # ---- labels: record_plan names the op before entering a kernel ----

    @contextlib.contextmanager
    def label(self, name: str) -> Iterator[None]:
        self._labels.append(name)
        try:
            yield
        finally:
            self._labels.pop()

    def current_label(self, default: str) -> str:
        return f"{self._labels[-1]}/{default}" if self._labels else default

    # ---- record/measure ----

    @property
    def suppressed(self) -> bool:
        return self._suppressed > 0

    def add(self, trace: KernelTrace) -> None:
        if not self.suppressed:
            self.records.append(trace)

    def seconds_to_cycles(self, seconds: float) -> int:
        return max(1, int(round(seconds * self.clock_hz)))

    def measure(self, fn: Callable[[], object], *, op: str, kind: str,
                mode: str = "", grid: Tuple[int, ...] = (),
                block_q: int = 0, block_kv: int = 0, hbm_bytes: int = 0,
                flops: int = 0) -> object:
        """Run ``fn`` (warmup + iters), record the median wall time as one
        op-level ``KernelTrace``, and return the *last* result.  Nested
        kernel-level instrumentation is suppressed for the duration."""
        import jax
        self._suppressed += 1
        try:
            out = None
            for _ in range(self.warmup):
                out = jax.block_until_ready(fn())
            times = []
            for _ in range(self.iters):
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn())
                times.append(time.perf_counter() - t0)
            times.sort()
            wall = times[len(times) // 2]
        finally:
            self._suppressed -= 1
        self.records.append(KernelTrace(
            op=op, kind=kind, mode=mode, grid=tuple(grid),
            block_q=block_q, block_kv=block_kv,
            cycles=self.seconds_to_cycles(wall), hbm_bytes=hbm_bytes,
            wall_time_s=wall, flops=flops, clock_hz=self.clock_hz,
            source="wall_time"))
        return out

    def by_op(self) -> Dict[str, KernelTrace]:
        """Latest record per op name (kernel-level ``parent/kernel``
        sub-records keep their slash-labels and never shadow op names)."""
        return {t.op: t for t in self.records}


_ACTIVE: List[KernelRecorder] = []


def active_recorder() -> Optional[KernelRecorder]:
    """The innermost active recorder, or None (the common case — the
    instrumented kernels call this on every invocation)."""
    return _ACTIVE[-1] if _ACTIVE else None


def recorder_for(*arrays) -> Optional[KernelRecorder]:
    """Kernel-side hook: the active recorder iff recording applies to
    this call — none active, nested under a ``measure`` (already being
    timed at op level), or abstract/traced operands (nothing to time
    under ``jit``) all return None.  The kernels consult this through
    ``sys.modules`` so an un-imported replay module costs them nothing."""
    rec = active_recorder()
    if rec is None or rec.suppressed:
        return None
    import jax
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        return None
    return rec


@contextlib.contextmanager
def recording(recorder: Optional[KernelRecorder] = None, *,
              clock_hz: float = DEFAULT_CLOCK_HZ) -> Iterator[KernelRecorder]:
    """Activate a recorder for the dynamic extent of the block."""
    rec = recorder if recorder is not None else KernelRecorder(clock_hz)
    _ACTIVE.append(rec)
    try:
        yield rec
    finally:
        _ACTIVE.pop()


# ---------------------------------------------------------------------------
# record_plan: drive a plan's op list through the real kernels
# ---------------------------------------------------------------------------

def record_plan(plan, *, ops: Optional[Sequence[str]] = None,
                max_ops: Optional[int] = None, use_pallas: bool = False,
                iters: int = 1, warmup: int = 1,
                clock_hz: float = DEFAULT_CLOCK_HZ, seed: int = 0,
                dtype=None):
    """Execute each planned op's kernel at the plan's own geometry
    (batch 1) under a recorder and return ``(traced_plan, recorder)``.

    ``ops`` restricts recording to the named plan ops; ``max_ops`` caps
    the count (plan order, attention before gemms) — untraced ops keep
    analytic lowering at replay time, which is exactly the mixed-plan
    contract the tests pin.  Plan at a small ``seq_len`` first: recording
    runs real kernels, so a paper-sized plan is minutes of CPU time.

    Byte accounting: recorded ``hbm_bytes`` are the executed arrays'
    host I/O (gemms: x + w + out, matching the kernel-level ``tile_gemm``
    records; attention: the mode's analytic traffic at the actual shapes
    and dtype).  For streamed-mode gemms this intentionally differs from
    the analytic simulator, which keeps their activations on-chip (zero
    HBM bytes) — replayed byte counts reflect the measurement, so compare
    traced-vs-analytic *cycles* (what ``fit_calibration`` does), not
    bytes, across that convention boundary.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    dtype = dtype or jnp.float32
    rec = KernelRecorder(clock_hz, iters=iters, warmup=warmup)
    wanted = set(ops) if ops is not None else None

    def selected(name: str, taken: int) -> bool:
        if wanted is not None and name not in wanted:
            return False
        return max_ops is None or taken < max_ops

    key = jax.random.PRNGKey(seed)
    taken = 0
    with recording(rec):
        for lp in plan.layers:
            if not selected(lp.name, taken):
                continue
            taken += 1
            key, kq, kx, kk, kv = jax.random.split(key, 5)
            q = jax.random.normal(kq, (1, lp.heads, lp.seq_q, lp.head_dim),
                                  dtype)
            x_kv = jax.random.normal(kx, (1, lp.seq_kv, lp.d_kv), dtype)
            wk = jax.random.normal(kk, (lp.d_kv, lp.kv_heads, lp.head_dim),
                                   dtype)
            wv = jax.random.normal(kv, (lp.d_kv, lp.kv_heads, lp.head_dim),
                                   dtype)
            kops.attention_by_plan(lp, q, x_kv, wk, wv,
                                   use_pallas=use_pallas)
        for g in plan.gemms:
            if not selected(g.name, taken):
                continue
            taken += 1
            key, kx, kw = jax.random.split(key, 3)
            x = jax.random.normal(kx, (g.m, g.k), dtype)
            w = jax.random.normal(kw, (g.k, g.n), dtype)
            itemsize = jnp.dtype(dtype).itemsize
            # The tile grid the pallas path launches at tile_gemm's
            # default blocks (the jnp path is the same math untiled).
            bm, bn, bk = min(256, g.m), min(256, g.n), min(512, g.k)
            grid = (-(-g.n // bn), -(-g.m // bm), -(-g.k // bk))
            with rec.label(g.name):
                rec.measure(
                    lambda x=x, w=w: kops.projection(
                        x, w, use_pallas=use_pallas),
                    op=g.name, kind="gemm", mode=g.mode.value,
                    grid=grid, block_q=bm, block_kv=bn,
                    hbm_bytes=(g.m * g.k + g.k * g.n
                               + g.m * g.n) * itemsize,
                    flops=2 * g.m * g.k * g.n)
    return plan.attach_traces(rec.records), rec


# ---------------------------------------------------------------------------
# CalibrationReport + fitting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CalibrationReport:
    """Analytic-vs-recorded error per op class + fitted per-resource cycle
    scale factors (DESIGN.md §10).

    ``per_class[kind]`` carries ``count`` / ``analytic_cycles`` /
    ``recorded_cycles`` / ``ratio`` (recorded/analytic totals) /
    ``mean_abs_rel_err`` over the traced ops of that class.  ``scale``
    maps simulator resources to multiplicative cycle factors; apply with
    ``simulate_plan(plan, calibration=report)`` or sweep with
    ``repro.dse.run_sweep(calibrations=(None, report))``.
    """

    name: str
    model: str
    hw: str
    clock_hz: float
    per_class: Mapping[str, Mapping[str, float]]
    scale: Mapping[str, float]

    def __post_init__(self):
        for r, s in self.scale.items():
            if s <= 0:
                raise ValueError(f"{self.name}: scale[{r!r}] must be > 0, "
                                 f"got {s!r}")

    @property
    def traced_ops(self) -> int:
        return int(sum(c.get("count", 0) for c in self.per_class.values()))

    def ratio(self, kind: str) -> float:
        """Recorded/analytic cycle ratio for one op class (1.0 = the
        analytic model already matches the recording)."""
        return float(self.per_class[kind]["ratio"])

    def to_dict(self) -> Dict[str, object]:
        return {
            "version": KERNEL_TRACE_VERSION,
            "name": self.name, "model": self.model, "hw": self.hw,
            "clock_hz": self.clock_hz,
            "per_class": {k: dict(v) for k, v in self.per_class.items()},
            "scale": dict(self.scale),
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: Mapping[str, object]) -> "CalibrationReport":
        if d.get("version") != KERNEL_TRACE_VERSION:
            raise ValueError(
                f"unsupported CalibrationReport version {d.get('version')!r}")
        return cls(name=d["name"], model=d["model"], hw=d["hw"],
                   clock_hz=float(d["clock_hz"]),
                   per_class={k: dict(v)
                              for k, v in d["per_class"].items()},
                   scale={k: float(v) for k, v in d["scale"].items()})

    @classmethod
    def from_json(cls, s: str) -> "CalibrationReport":
        return cls.from_dict(json.loads(s))


def _traced_ops(plan) -> List[Tuple[str, KernelTrace]]:
    out = []
    for lp in tuple(plan.layers) + tuple(plan.gemms):
        tr = getattr(lp, "trace", None)
        if tr is not None:
            out.append((lp.name, tr))
    return out


def analytic_op_profile(plan, hw=None) -> Dict[str, Dict[str, object]]:
    """Per-op analytic timing decomposition: simulate the plan with replay
    *off* and reduce the event trace to ``{op: {"span": elapsed cycles,
    "busy": {resource: busy cycles}}}`` — the denominator side of every
    calibration fit."""
    from repro.sim.pipeline import simulate_plan
    res = simulate_plan(plan, hw=hw, replay=False)
    prof: Dict[str, Dict[str, object]] = {}
    for e in res.trace.events:
        p = prof.setdefault(e.op, {"start": e.start, "end": e.end,
                                   "busy": {}})
        p["start"] = min(p["start"], e.start)
        p["end"] = max(p["end"], e.end)
        p["busy"][e.resource] = p["busy"].get(e.resource, 0) + e.cycles
    return {op: {"span": p["end"] - p["start"], "busy": p["busy"]}
            for op, p in prof.items()}


def fit_calibration(plan, hw=None, *, name: Optional[str] = None,
                    ridge: float = 1e-3) -> CalibrationReport:
    """Fit a ``CalibrationReport`` from a plan's attached traces.

    Per-class error compares each traced op's recorded cycles with its
    analytic *span* (elapsed cycles under analytic lowering).  The
    per-resource scale solves ``recorded_i ~= sum_r busy[i][r] * s_r``
    by ridge-regularized least squares (prior: the global recorded/
    analytic-span ratio on every resource), so an under-determined
    system — few traced op shapes, many resources — degrades to the
    global ratio instead of oscillating.  Scales are clamped positive.
    """
    import numpy as np

    traced = _traced_ops(plan)
    if not traced:
        raise ValueError(f"{plan.model}: no attached KernelTrace records — "
                         "record_plan / attach_traces first")
    prof = analytic_op_profile(plan, hw=hw)
    hw_name = hw.name if hw is not None else plan.hw

    resources = sorted({r for op, _ in traced
                        for r in prof[op]["busy"]})
    a = np.zeros((len(traced), len(resources)))
    b = np.zeros(len(traced))
    per_class: Dict[str, Dict[str, float]] = {}
    for i, (op, tr) in enumerate(traced):
        span = prof[op]["span"]
        b[i] = tr.cycles
        for j, r in enumerate(resources):
            a[i, j] = prof[op]["busy"].get(r, 0)
        c = per_class.setdefault(tr.kind, {
            "count": 0, "analytic_cycles": 0, "recorded_cycles": 0,
            "abs_rel_err_sum": 0.0})
        c["count"] += 1
        c["analytic_cycles"] += span
        c["recorded_cycles"] += tr.cycles
        c["abs_rel_err_sum"] += abs(tr.cycles - span) / max(span, 1)

    total_ana = sum(c["analytic_cycles"] for c in per_class.values())
    total_rec = sum(c["recorded_cycles"] for c in per_class.values())
    prior = total_rec / max(total_ana, 1)
    for c in per_class.values():
        c["ratio"] = c["recorded_cycles"] / max(c["analytic_cycles"], 1)
        c["mean_abs_rel_err"] = c.pop("abs_rel_err_sum") / c["count"]

    # Ridge-regularized normal equations around the global-ratio prior.
    ata = a.T @ a
    lam = ridge * max(float(np.trace(ata)) / max(len(resources), 1), 1.0)
    sol = np.linalg.solve(ata + lam * np.eye(len(resources)),
                          a.T @ b + lam * prior * np.ones(len(resources)))
    scale = {r: float(max(s, 1e-9)) for r, s in zip(resources, sol)}

    clock = traced[0][1].clock_hz
    return CalibrationReport(
        name=name or f"{plan.model}@{plan.shape}-{hw_name}",
        model=plan.model, hw=hw_name, clock_hz=clock,
        per_class=per_class, scale=scale)


def resolve_calibration(calibration) -> Optional[Mapping[str, float]]:
    """Normalize a ``simulate_plan(calibration=...)`` argument — a
    ``CalibrationReport``, a raw ``{resource: factor}`` mapping, or None —
    into the scale mapping the engine applies."""
    if calibration is None:
        return None
    scale = getattr(calibration, "scale", calibration)
    if not isinstance(scale, Mapping):
        raise TypeError(f"calibration must be a CalibrationReport or a "
                        f"resource->factor mapping, got {calibration!r}")
    return scale


# ---------------------------------------------------------------------------
# Optional cost-analysis timing source (XLA flop estimate -> cycles)
# ---------------------------------------------------------------------------

def cost_analysis_cycles(fn: Callable, *args, hw=None) -> Tuple[int, int]:
    """(cycles, flops) for one kernel call from XLA's compiled
    ``cost_analysis()`` instead of wall time: flops divided by the design
    point's aggregate INT8 MAC throughput (``EnergyModel
    .macro_ops_per_cycle`` x ``num_macros``).  The deterministic timing
    source for CI — no wall-clock noise."""
    import jax

    from repro.configs.hardware import STREAMDCIM_BASE
    from repro.sim.energy import STREAMDCIM_ENERGY_BASE

    hw = hw or STREAMDCIM_BASE
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    flops = int(ca.get("flops", 0.0))
    per_cycle = (STREAMDCIM_ENERGY_BASE.macro_ops_per_cycle(hw)
                 * hw.num_macros)
    return max(1, math.ceil(flops / max(per_cycle, 1.0))), flops
