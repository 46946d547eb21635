"""Slot-level continuous-batching serving engine (DESIGN.md §11).

The engine owns ``slots`` independent decode slots, each holding its own
KV cache (batch dim 1) and position; every engine step it (1) admits
arrived requests into free slots — *while other slots are mid-decode*,
no wave draining — running each admission's prefill under its
planner-resolved ``ExecutionPlan`` (per-layer modes dispatched through
``kernels.ops.attention_by_plan``, heterogeneous plans included), then
(2) advances every already-active slot by one token, and (3) recycles a
slot the moment its request's token budget is spent — a short request
never pads out to a long neighbour's length.

Decode is *batched* (DESIGN.md §15): active slots' caches live in a
paged K/V pool (``repro.serve.kv_cache.PagedKVCache``), and each step
the engine gathers every decoding slot, whatever its K/V length, into
one packed cache and advances it with a single ``decode_step`` call, so
the step streams the weights once; each row decodes at its own position
and is masked by its own length (``cache["len"]`` of shape ``(B,)``).
``decode_batches`` counts those calls (one per step that decodes) while
``decode_calls`` keeps counting per-slot token advances, so
``decode_calls / decode_batches`` is the dispatch amplification the
batching removes; ``decode_ragged`` counts the calls whose rows hold
more than one K/V length.  Cache trees the pool cannot page (SSM /
hybrid / MLA / enc-dec state, or mesh-sharded serving) transparently
fall back to the per-slot B=1 path with identical semantics.

The step timeline is the *shared* deterministic schedule
(``repro.serve.schedule.build_schedule``), the same object
``repro.sim.simulate_serve`` lowers through the cycle-approximate
simulator — so the simulator reproduces this engine's per-request decode
step counts exactly, and each decode step's ``DecodePlan``
(``repro.plan.plan_decode_step``) carries the predicted HBM bytes the
simulator cross-asserts.

Plans are compiled on admission from a bounded LRU cache
(``plan_cache_size``); the queue is a ``collections.deque`` — long-running
servers neither re-scan the queue per admission nor grow the plan cache
without limit.  The legacy ``mode=`` kwarg remains as a deprecation shim
that bypasses the planner.

Tracing (``repro.obs.spans``): ``run`` writes host spans into the
profiler's trace, on the device planes' clock, at every layer boundary —

    engine.step            one per schedule step (``step_num``)
      engine.admit         one per admitted request (``rid``)
        engine.prefill     dispatch of the model's prefill
        engine.sync        the read of token #1
        kv.write           ``PagedKVCache.admit`` (its length read an
                           ``engine.sync``)
      engine.plan_decode   ``decode_plan_for``
      engine.decode        one per decode dispatch (``B``, ``kv``: the
                           widest K/V length of the dispatch)
        kv.gather          ``PagedKVCache.gather``
        model.decode_step  dispatch of the jitted ``decode_step``
        kv.scatter         ``PagedKVCache.scatter`` (its rows' lengths
                           read in one ``engine.sync``)
        engine.sync        the read of the step's tokens
      engine.retire        freeing finished slots

(the per-slot fallback opens ``engine.decode``, ``model.decode_step`` and
``engine.sync`` per slot) — and counts, per ``run``, in ``registry``:
``steps``, ``decode_calls``, ``decode_batches``, ``decode_ragged``,
``admissions`` and ``host_syncs`` (every device-to-host read of the
loop, each through ``obs.spans.host_read``).  Device programs carry
stable names: ``jit_decode_step`` here, ``jit_kv_gather``/
``jit_kv_scatter``/``jit_kv_write`` in the pool.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core.types import ExecutionMode, ModelConfig
from repro.obs.metrics import (METRICS_SCHEMA_VERSION, MetricsRegistry,
                               RequestSpan, observe_spans, spans_from_steps,
                               spans_from_timeline, summarize_spans)
from repro.obs.spans import host_read, span, step_span
from repro.serve.kv_cache import PagedKVCache
from repro.serve.schedule import Schedule, ServeRequest, build_schedule


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    arrival_step: int = 0         # engine step the request becomes visible
    out_tokens: Optional[List[int]] = None


@dataclasses.dataclass(frozen=True)
class StepRecord:
    """What one engine step actually executed (the engine-side half of
    the engine==simulator agreement tests)."""

    step: int
    admitted: Tuple[int, ...]            # rids prefilled
    decoded: Tuple[int, ...]             # rids advanced one token
    kv_lens: Tuple[int, ...]             # per decoded slot: attended KV len
    decode_plan: Optional[object] = None  # the step's DecodePlan (or None)
    # The step's batched decode_step call, as one (kv, rids) entry: kv is
    # the widest K/V length of its rows (rids in dispatch order); None
    # when the step decoded nothing and on the per-slot fallback path.
    buckets: Optional[Tuple[Tuple[int, Tuple[int, ...]], ...]] = None


class _LRU:
    """Tiny bounded LRU mapping (OrderedDict-backed)."""

    def __init__(self, maxsize: int) -> None:
        self.maxsize = max(int(maxsize), 1)
        self._d: "OrderedDict[Any, Any]" = OrderedDict()

    def get(self, key):
        if key not in self._d:
            return None
        self._d.move_to_end(key)
        return self._d[key]

    def put(self, key, value) -> None:
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key) -> bool:
        return key in self._d


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 512,
                 plan=None,
                 plan_cache_size: int = 32,
                 plan_decode: bool = True,
                 mode: Optional[ExecutionMode] = None,
                 mesh=None,
                 batch_decode: bool = True,
                 page_size: int = 64,
                 clock=time.perf_counter):
        """``plan``: an ``repro.plan.ExecutionPlan`` to serve under (pins
        every admission); default: re-plan per admitted prompt length from
        a bounded LRU cache.  Prefill plans and per-step ``DecodePlan``s
        each get their own LRU of ``plan_cache_size`` entries (up to 2x
        ``plan_cache_size`` plans total).  ``plan_decode=False`` skips
        per-step
        ``DecodePlan`` compilation (pure-throughput serving; step records
        then carry no plan).  ``mode``: deprecated explicit override
        (pre-PR-2 API) — skips the planner entirely.  ``mesh``: a jax
        mesh (``launch.mesh`` builders); prefill/decode then run under
        ``shard_map`` with replicated specs (``repro.shard.serve``,
        DESIGN.md §13) — numerics identical to the mesh-less path.
        ``batch_decode``: advance all active slots, of any K/V
        lengths, with one ``decode_step`` call a step through a paged
        K/V pool of ``page_size`` positions per page (DESIGN.md §15);
        auto-falls back per slot for cache trees the pool cannot page
        and under ``mesh``.
        ``clock``: wall-time source (``time.perf_counter``-compatible)
        for the ``"wall"`` SLO stats — injectable so tests can pin
        percentiles deterministically."""
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.plan = plan
        self.plan_decode = plan_decode
        self._forced_mode = mode
        self._plan_cache = _LRU(plan_cache_size)
        # Decode plans get their own bound: their keys (kv-length tuples)
        # change almost every step, and sharing one LRU would let that
        # churn evict the highly-reusable per-prompt-length prefill plans.
        self._decode_plan_cache = _LRU(plan_cache_size)
        self.mod = registry.model_module(cfg)
        self._prefill_takes_plan = (
            hasattr(self.mod, "prefill")
            and "plan" in inspect.signature(self.mod.prefill).parameters)
        self.mesh = mesh
        if mesh is not None:
            from repro.shard.serve import mesh_decode_fn
            self._decode = mesh_decode_fn(self.mod, cfg, mesh)
        else:
            mod = self.mod

            def decode_step(p, c, t):
                return mod.decode_step(p, cfg, c, t)

            self._decode = jax.jit(decode_step)
        # Batched decode: mesh serving keeps per-slot B=1 calls (the
        # shard_map decode fn is traced for that shape); otherwise the
        # first admission decides — if its cache tree pages, the run
        # serves through the pool, else it falls back per slot.
        self.batch_decode = batch_decode and mesh is None
        self.page_size = page_size
        self._pool: Optional[PagedKVCache] = None
        self._clock = clock
        self._queue: deque = deque()
        self.step_log: List[StepRecord] = []
        self.decode_calls = 0         # per-slot token advances
        self.decode_batches = 0       # actual decode_step invocations
        self.last_schedule: Optional[Schedule] = None
        # Observability (DESIGN.md §12): per-run lifecycle bookkeeping.
        self.registry = MetricsRegistry()
        self._syncs = self.registry.counter("host_syncs")
        self._ragged = self.registry.counter("decode_ragged")
        self._arrivals: Dict[int, int] = {}
        self._step_walls: Dict[int, Tuple[float, float]] = {}
        self._prefill_wall_end: Dict[int, float] = {}

    def submit(self, req: Request) -> None:
        # The cache peaks at prompt + max_new - 1 entries (the last
        # emitted token is never written back).
        if len(req.prompt) + req.max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) - 1 exceeds the "
                f"engine's max_len ({self.max_len})")
        req.out_tokens = []
        self._queue.append(req)

    # ------------------------------------------------------------------
    # Planning
    # ------------------------------------------------------------------

    def plan_for(self, seq_len: int):
        """The ``ExecutionPlan`` governing an admission of prompt length
        ``seq_len`` (bounded-LRU cached per length).  A construction-time
        ``plan=`` wins; attention-free families have nothing to plan
        (None)."""
        if self.plan is not None:
            return self.plan
        if self.cfg.num_heads == 0:
            return None
        plan = self._plan_cache.get(seq_len)
        if plan is None:
            from repro.plan import plan_model
            plan = plan_model(self.cfg, seq_len=seq_len)
            self._plan_cache.put(seq_len, plan)
        return plan

    def decode_plan_for(self, kv_lens: Tuple[int, ...]):
        """The ``DecodePlan`` for one step whose active slots attend
        ``kv_lens`` (bounded-LRU cached per length tuple)."""
        if not self.plan_decode or self.cfg.num_heads == 0:
            return None
        key = tuple(kv_lens)
        dp = self._decode_plan_cache.get(key)
        if dp is None:
            from repro.plan import plan_decode_step
            # The deprecated mode= override bypasses the planner for
            # prefill; decode plans must honor it too, or step records
            # would contradict the mode the engine claims to serve under.
            dp = plan_decode_step(self.cfg, key, mode=self._forced_mode,
                                  force_mode=self._forced_mode is not None)
            self._decode_plan_cache.put(key, dp)
        return dp

    def mode_for(self, seq_len: int) -> ExecutionMode:
        """Planner-resolved prefill mode summary for one admission.
        Heterogeneous plans no longer collapse to this — prefill
        dispatches per layer (``prefill(plan=...)``); this accessor
        reports the uniform mode (or the first layer's, for
        heterogeneous plans) for logging and legacy callers."""
        if self._forced_mode is not None:       # deprecated explicit override
            return self._forced_mode
        plan = self.plan_for(seq_len)
        if plan is None or not plan.layers:
            return self.cfg.execution_mode
        return plan.uniform_mode or plan.layers[0].mode

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------

    def _prefill_one(self, req: Request):
        """Prefill one request into a fresh slot cache (B=1, unpadded —
        per-request numerics never depend on the neighbours)."""
        toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None, :])
        plan = self.plan_for(len(req.prompt))
        kwargs: Dict[str, Any] = {}
        if self._forced_mode is not None:
            kwargs["mode"] = self._forced_mode
        elif plan is not None and self._prefill_takes_plan:
            kwargs["plan"] = plan
        else:
            kwargs["mode"] = self.mode_for(len(req.prompt))
        if self.mesh is not None:
            from repro.shard.serve import mesh_prefill
            logits, cache = mesh_prefill(
                self.mod, self.params, self.cfg, {"tokens": toks},
                mesh=self.mesh, max_len=self.max_len, **kwargs)
        else:
            logits, cache = self.mod.prefill(
                self.params, self.cfg, {"tokens": toks},
                max_len=self.max_len, **kwargs)
        return logits[:, -1], cache

    def run(self, *, greedy: bool = True) -> List[Request]:
        """Drain the queue under the continuous-batching schedule;
        returns completed requests in completion order.

        Every step admits into any free slot (other slots keep decoding),
        decodes each active slot once, and recycles finished slots
        immediately — a request with ``n`` output tokens consumes exactly
        ``n - 1`` decode steps regardless of its neighbours.
        """
        del greedy                              # argmax sampling only
        reqs = list(self._queue)
        self._queue.clear()
        schedule = build_schedule(
            [ServeRequest(r.rid, len(r.prompt), r.max_new_tokens,
                          r.arrival_step) for r in reqs],
            self.slots)
        self.last_schedule = schedule
        by_rid = {r.rid: r for r in reqs}
        slot_state: Dict[int, Dict[str, Any]] = {}
        rid_slot: Dict[int, int] = {}
        done: List[Request] = []
        self.step_log = []
        self.decode_calls = 0
        self.decode_batches = 0
        self._pool = None
        batched = self.batch_decode
        self.registry = MetricsRegistry()
        self._syncs = self.registry.counter("host_syncs")
        self._ragged = self.registry.counter("decode_ragged")
        self._arrivals = {r.rid: r.arrival_step for r in reqs}
        self._step_walls = {}
        self._prefill_wall_end = {}
        V = self.cfg.vocab_size
        for st in schedule.steps:
            with step_span("engine.step", step_num=st.step):
                wall0 = self._clock()
                for slot, rid in st.admitted:
                    with span("engine.admit", rid=rid):
                        batched = self._admit(slot, by_rid[rid], batched,
                                              slot_state, V)
                    rid_slot[rid] = slot
                dp = None
                step_buckets = None
                if st.decoding:
                    kv_lens = tuple(kv for _, _, kv in st.decoding)
                    with span("engine.plan_decode"):
                        dp = self.decode_plan_for(kv_lens)
                    if self._pool is not None:
                        step_buckets = self._decode_batch(
                            st, kv_lens, slot_state, V)
                    else:
                        self._decode_per_slot(st, slot_state, V)
                self.step_log.append(StepRecord(
                    step=st.step,
                    admitted=tuple(r for _, r in st.admitted),
                    decoded=tuple(r for _, r, _ in st.decoding),
                    kv_lens=tuple(kv for _, _, kv in st.decoding),
                    decode_plan=dp,
                    buckets=step_buckets))
                self._step_walls[st.step] = (wall0, self._clock())
                with span("engine.retire"):
                    for rid in st.finished:
                        done.append(by_rid[rid])
                        slot = rid_slot.pop(rid)
                        if self._pool is not None:
                            self._pool.free(slot)       # recycle the pages
                        del slot_state[slot]            # recycle the slot
        self.registry.counter("steps").inc(len(self.step_log))
        self.registry.counter("decode_calls").inc(self.decode_calls)
        self.registry.counter("decode_batches").inc(self.decode_batches)
        self.registry.counter("admissions").inc(
            sum(len(rec.admitted) for rec in self.step_log))
        observe_spans(self.registry, self.request_spans, "steps.")
        observe_spans(self.registry, self.wall_spans, "wall.")
        return done

    def _admit(self, slot: int, r: Request, batched: bool,
               slot_state: Dict[int, Dict[str, Any]], V: int) -> bool:
        """Prefill ``r`` into ``slot`` and emit its token #1; returns
        whether the run still decodes through the pool."""
        with span("engine.prefill"):
            last_logits, cache = self._prefill_one(r)
        tok = jnp.argmax(last_logits[:, :V], axis=-1)[:, None]
        r.out_tokens.append(int(host_read(tok, self._syncs)[0, 0]))
        # Token #1 just materialized: the wall-clock TTFT mark.
        self._prefill_wall_end[r.rid] = self._clock()
        if batched and self._pool is None:
            # First admission decides for the run: page the pool or fall
            # back per slot (SSM/MLA/hybrid/enc-dec trees — every later
            # cache shares the config).
            if PagedKVCache.supports(cache):
                self._pool = PagedKVCache.from_cache(
                    cache, slots=self.slots, page_size=self.page_size,
                    syncs=self._syncs)
            else:
                batched = False
        if self._pool is not None:
            self._pool.admit(slot, cache)
            # The pool owns the K/V now; the pooled step feeds each slot's
            # last token from ``out_tokens``.
            cache = tok = None
        slot_state[slot] = {"req": r, "cache": cache, "tok": tok}
        return batched

    def _decode_per_slot(self, st, slot_state, V) -> None:
        """Advance each active slot with its own B=1 ``decode_step``
        (cache trees the pool cannot page, and mesh serving)."""
        for slot, _rid, kv in st.decoding:
            with span("engine.decode", B=1, kv=kv):
                ss = slot_state[slot]
                with span("model.decode_step"):
                    logits, ss["cache"] = self._decode(
                        self.params, ss["cache"], ss["tok"])
                self.decode_calls += 1
                self.decode_batches += 1
                tok = jnp.argmax(logits[:, 0, :V], axis=-1)[:, None]
                ss["tok"] = tok
                ss["req"].out_tokens.append(
                    int(host_read(tok, self._syncs)[0, 0]))

    def decode_wall_s(self) -> float:
        """Wall seconds spent in pure-decode steps (steps that also
        prefilled are excluded, so prefill wall never pollutes the
        decode-phase number).  The denominator for decode throughput:
        batching cuts dispatch here, while prefill cost is identical on
        both paths and dominates short-generation end-to-end walls."""
        total = 0.0
        for rec in self.step_log:
            if rec.decoded and not rec.admitted:
                bounds = self._step_walls.get(rec.step)
                if bounds is not None:
                    total += bounds[1] - bounds[0]
        return total

    def _decode_batch(self, st, kv_lens, slot_state, V):
        """Advance all of one step's active slots with one
        ``decode_step`` call through the paged pool, each row at its own
        K/V length; returns the step's ``((max kv, rids),)`` record."""
        slots = [slot for slot, _, _ in st.decoding]
        rids = tuple(rid for _, rid, _ in st.decoding)
        kv = max(kv_lens)
        with span("engine.decode", B=len(slots), kv=kv):
            # Each row's schedule KV length counts the token being
            # decoded; its cache holds everything before it.
            for s, k in zip(slots, kv_lens):
                if self._pool.len_of(s) + 1 != k:
                    raise RuntimeError(
                        f"slot {s}: cache len {self._pool.len_of(s)} "
                        f"inconsistent with scheduled kv {k}")
            cache = self._pool.gather(slots)
            # Each slot's last token is on the host already: one
            # transfer, not a device slice and a concatenate per row.
            toks = jnp.asarray([[slot_state[s]["req"].out_tokens[-1]]
                                for s in slots], jnp.int32)
            with span("model.decode_step"):
                logits, cache = self._decode(self.params, cache, toks)
            self._pool.scatter(slots, cache)
            self.decode_batches += 1
            self.decode_calls += len(slots)
            if len(set(kv_lens)) > 1:
                self._ragged.inc()
            tok = jnp.argmax(logits[:, 0, :V], axis=-1)
            for s, t in zip(slots, host_read(tok, self._syncs).tolist()):
                slot_state[s]["req"].out_tokens.append(t)
        return ((kv, rids),)

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    @property
    def plan_cache_len(self) -> int:
        return len(self._plan_cache)

    @property
    def request_spans(self) -> List[RequestSpan]:
        """Step-domain lifecycle spans derived from the *executed*
        ``step_log`` — the engine-side half of the serving-metrics parity
        check (``obs.metrics.assert_serve_parity``, DESIGN.md §12)."""
        return spans_from_steps(self.step_log, self._arrivals)

    @property
    def wall_spans(self) -> List[RequestSpan]:
        """Wall-clock lifecycle spans (seconds) from the per-step
        timestamps the last ``run`` recorded: first token at the instant
        each admission's prefill materialized token #1, finish at the end
        of the request's last step."""
        if not self._step_walls:
            return []
        admit: Dict[int, int] = {}
        last: Dict[int, int] = {}
        decodes: Dict[int, int] = {}
        for rec in self.step_log:
            for rid in rec.admitted:
                admit[rid] = rec.step
                last[rid] = rec.step
                decodes.setdefault(rid, 0)
            for rid in rec.decoded:
                decodes[rid] = decodes.get(rid, 0) + 1
                last[rid] = rec.step
        return spans_from_timeline(admit, last, decodes, self._arrivals,
                                   self._step_walls,
                                   self._prefill_wall_end, unit="seconds")

    def stats(self) -> Dict[str, object]:
        """Summary of the last ``run``: step count, per-request decode
        steps, admission/finish steps, plus the serving SLO summaries —
        step-domain TTFT/TPOT/queue-delay/e2e p50/p95/p99 at the top
        level (directly comparable with ``ServeSimResult.metrics`` via
        ``obs.metrics.assert_serve_parity``), wall-clock summaries under
        ``"wall"``, and the raw registry under ``"metrics"``.

        Step and decode counts are derived from ``step_log`` — what the
        engine *executed* — not from the schedule it planned to execute,
        so an execution bug cannot hide behind a correct schedule (the
        simulator lowers the same schedule; comparing executed-vs-sim is
        the meaningful check).  Before any ``run`` — or after a
        zero-request run — every field is a well-defined zero/empty,
        never a division error."""
        s = self.last_schedule
        decode_steps: Dict[int, int] = {
            rid: 0 for rid in (s.decode_steps if s is not None else {})}
        for rec in self.step_log:
            for rid in rec.decoded:
                decode_steps[rid] = decode_steps.get(rid, 0) + 1
        out: Dict[str, object] = {
            "schema_version": METRICS_SCHEMA_VERSION,
            "steps": len(self.step_log),
            "decode_steps": decode_steps,
            "admit_step": dict(s.admit_step) if s is not None else {},
            "finish_step": dict(s.finish_step) if s is not None else {},
            "decode_calls": self.decode_calls,
            "decode_batches": self.decode_batches,
            "max_concurrency": max(
                (len(r.admitted) + len(r.decoded) for r in self.step_log),
                default=0),
            "plan_cache_len": self.plan_cache_len,
        }
        out.update(summarize_spans(self.request_spans, unit="steps"))
        out["wall"] = summarize_spans(self.wall_spans, unit="seconds")
        out["metrics"] = self.registry.to_dict()
        return out
