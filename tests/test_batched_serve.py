"""Batched paged-KV serving (DESIGN.md §15): the paged pool, shape
buckets, bucketed decode planning, the engine's batched decode path, and
the coarse==fine serve-sim equivalence.

Invariant map:

* ``PagedKVCache`` gather→scatter round-trips are value-exact, for slots
  of equal or unequal K/V lengths, write nothing into another slot's
  pages, and pages allocate/free with slot lifecycle (a leak would
  exhaust the pool);
* ``plan_decode_buckets`` partitions the whole-step plan exactly —
  per-bucket HBM predictions sum to ``plan_decode_step``'s;
* the batched engine emits token-for-token what the per-slot engine
  emits (row independence end-to-end), while issuing one dispatch per
  step that decodes, whatever its slots' K/V lengths
  (``decode_ragged`` counts the mixed ones); non-pageable cache trees
  (SSM/hybrid) fall back transparently;
* ``simulate_serve(decode_lowering="coarse")`` reproduces fine's
  cycles, bytes, and metrics *exactly* with strictly fewer trace events.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.types import ExecutionMode as EM
from repro.plan import plan_decode_buckets, plan_decode_step
from repro.serve.engine import Engine, Request
from repro.serve.kv_cache import PagedKVCache, shape_buckets
from repro.serve.schedule import ServeRequest
from repro.sim import simulate_serve

SMOKE = registry.get_config("starcoder2-7b", smoke=True)
SLIDING = registry.get_config("h2o-danube3-4b", smoke=True)


def _params(cfg=SMOKE):
    return registry.model_module(cfg).init(jax.random.PRNGKey(0), cfg)


def _requests(cfg, *, n=6, seed=3, arrival_spread=3, max_new=(2, 6),
              plen=(3, 10)):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(int(rng.integers(*plen)),)
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(*max_new)),
                    arrival_step=int(rng.integers(0, arrival_spread)))
            for i in range(n)]


# ---------------------------------------------------------------------------
# shape_buckets / PagedKVCache
# ---------------------------------------------------------------------------

def test_shape_buckets_order_preserving():
    assert shape_buckets([5, 3, 5, 3, 7]) == [
        (5, (0, 2)), (3, (1, 3)), (7, (4,))]
    assert shape_buckets([4]) == [(4, (0,))]
    with pytest.raises(ValueError):
        shape_buckets([3, 0])


def _cache(L=2, Hkv=2, W=24, hd=8, length=9, seed=0):
    rng = np.random.default_rng(seed)
    return {"layers": {
                "k": jnp.asarray(rng.normal(size=(L, 1, Hkv, W, hd)),
                                 jnp.float32),
                "v": jnp.asarray(rng.normal(size=(L, 1, Hkv, W, hd)),
                                 jnp.float32)},
            "len": jnp.asarray(length, jnp.int32)}


def test_paged_pool_roundtrip_and_growth():
    pool = PagedKVCache(slots=3, num_layers=2, kv_heads=2, width=24,
                        head_dim=8, dtype=jnp.float32, page_size=8)
    c0, c1 = _cache(length=9, seed=0), _cache(length=9, seed=1)
    pool.admit(0, c0)
    pool.admit(1, c1)
    assert pool.pages_in_use == 4                 # ceil(9/8) = 2 each
    g = pool.gather([0, 1])
    assert g["layers"]["k"].shape == (2, 2, 2, 24, 8)
    assert g["len"].tolist() == [9, 9]
    # valid prefix round-trips exactly, per slot
    assert jnp.array_equal(g["layers"]["k"][:, 0, :, :9],
                           c0["layers"]["k"][:, 0, :, :9])
    assert jnp.array_equal(g["layers"]["v"][:, 1, :, :9],
                           c1["layers"]["v"][:, 0, :, :9])
    # grow across a page boundary: 9 -> 17 needs a third page per slot
    cur = g
    for new_len in range(10, 18):
        cur = {"layers": {
                   "k": cur["layers"]["k"].at[:, :, :, new_len - 1].set(1.0),
                   "v": cur["layers"]["v"].at[:, :, :, new_len - 1].set(2.0)},
               "len": jnp.asarray(new_len, jnp.int32)}
        pool.scatter([0, 1], cur)
        cur = pool.gather([0, 1])
    assert pool.pages_in_use == 6
    assert float(cur["layers"]["k"][0, 0, 0, 16, 0]) == 1.0
    pool.free(0)
    assert pool.pages_in_use == 3                 # slot 1 keeps its pages
    assert pool.len_of(1) == 17


def test_paged_pool_guards():
    pool = PagedKVCache(slots=2, num_layers=2, kv_heads=2, width=24,
                        head_dim=8, dtype=jnp.float32, page_size=8)
    pool.admit(0, _cache(length=5))
    with pytest.raises(ValueError, match="already admitted"):
        pool.admit(0, _cache(length=5))
    pool.admit(1, _cache(length=9))
    g = pool.gather([0, 1])                   # unequal lengths pack
    assert g["len"].tolist() == [5, 9]
    with pytest.raises(ValueError, match="shrink"):
        pool.scatter([0, 1], dict(g, len=jnp.asarray([6, 8], jnp.int32)))
    assert not PagedKVCache.supports({"layers": {"attn": 1, "ssm": 2},
                                      "len": 0})
    assert not PagedKVCache.supports(jnp.zeros(3))
    assert PagedKVCache.supports(_cache())


def test_paged_pool_ragged_roundtrip():
    """Slots of unequal lengths (one page, two pages, three pages) pack
    into one batch and write back as per-slot writes would: each slot's
    pages hold what one-slot gather/scatter leaves there, a slot outside
    the batch keeps its pages bitwise, and padding only reads and writes
    the spare page, which no slot is ever given."""
    def pool_with(lengths):
        pool = PagedKVCache(slots=4, num_layers=2, kv_heads=2, width=24,
                            head_dim=8, dtype=jnp.float32, page_size=8)
        for s, n in enumerate(lengths):
            pool.admit(s, _cache(length=n, seed=s))
        return pool

    def advance(cache):
        """One decode step's writes: a marker at each row's position."""
        n = cache["len"]
        at = jnp.arange(24)[None, None, None, :, None] \
            == n[None, :, None, None, None]
        layers = {side: jnp.where(at, val, cache["layers"][side])
                  for side, val in (("k", 1.5), ("v", -2.5))}
        return {"layers": layers, "len": n + 1}

    lengths = (5, 16, 9, 20)
    batched, solo = pool_with(lengths), pool_with(lengths)
    before = pool_with(lengths)
    rows = [0, 1, 2]                          # slot 3 sits out
    g = batched.gather(rows)
    assert g["len"].tolist() == [5, 16, 9]
    for i, s in enumerate(rows):
        n = lengths[s]
        assert jnp.array_equal(g["layers"]["k"][:, i, :, :n],
                               _cache(length=n, seed=s)["layers"]["k"]
                               [:, 0, :, :n])
    assert bool(jnp.isfinite(g["layers"]["k"]).all())
    batched.scatter(rows, advance(g))
    for s in rows:
        solo.scatter([s], advance(solo.gather([s])))
    assert batched.len_of(1) == 17 and batched.pages_in_use == 9
    spare = batched.num_pages
    for s in range(4):
        pages = batched.page_table(s)
        assert pages == solo.page_table(s) and spare not in pages
        for pool_b, pool_s in ((batched._k_pool, solo._k_pool),
                               (batched._v_pool, solo._v_pool)):
            assert jnp.array_equal(pool_b[jnp.asarray(pages)],
                                   pool_s[jnp.asarray(pages)])
    ids3 = jnp.asarray(before.page_table(3))
    assert jnp.array_equal(batched._k_pool[ids3], before._k_pool[ids3])
    # a second ragged step reads back what the first wrote
    g2 = batched.gather(rows)
    assert g2["len"].tolist() == [6, 17, 10]
    assert float(g2["layers"]["v"][0, 1, 0, 16, 0]) == -2.5


def test_paged_pool_exhaustion_is_loud():
    # slots=1 pool holds exactly ceil(16/8)=2 pages: a second full-width
    # admission (a slot leak) must fail loudly, not corrupt pages.
    pool = PagedKVCache(slots=1, num_layers=1, kv_heads=1, width=16,
                        head_dim=4, dtype=jnp.float32, page_size=8)
    pool.admit(0, _cache(L=1, Hkv=1, W=16, hd=4, length=16))
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.admit(1, _cache(L=1, Hkv=1, W=16, hd=4, length=16))


# ---------------------------------------------------------------------------
# plan_decode_buckets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ctx", [(9, 5, 9, 7, 5), (4,), (6, 6, 6)])
def test_plan_decode_buckets_partition_exact(ctx):
    buckets = plan_decode_buckets(SMOKE, ctx)
    whole = plan_decode_step(SMOKE, ctx)
    covered = sorted(p for slots, _ in buckets for p in slots)
    assert covered == list(range(len(ctx)))
    assert sum(p.total_hbm_bytes for _, p in buckets) \
        == whole.total_hbm_bytes
    assert sum(p.total_rewrite_cycles for _, p in buckets) \
        == whole.total_rewrite_cycles
    for slots, p in buckets:
        assert p.context == tuple(ctx[s] for s in slots)
        assert len(set(p.context)) == 1           # uniform bucket


def test_plan_decode_buckets_respects_mode_override():
    buckets = plan_decode_buckets(SMOKE, (5, 8, 5), mode=EM.NON_STREAM,
                                  force_mode=True)
    for _, p in buckets:
        assert all(lp.mode == EM.NON_STREAM for lp in p.layers)


# ---------------------------------------------------------------------------
# Engine: batched == per-slot, dispatch accounting, fallback
# ---------------------------------------------------------------------------

def _run_both(cfg, *, req_kw=None, eng_kw=None):
    req_kw = req_kw or {}
    eng_kw = eng_kw or {}
    outs = []
    for batch in (True, False):
        eng = Engine(cfg, _params(cfg), slots=3, max_len=32,
                     batch_decode=batch, **eng_kw)
        for r in _requests(cfg, **req_kw):
            eng.submit(r)
        done = eng.run()
        outs.append((eng, {r.rid: list(r.out_tokens) for r in done}))
    return outs


def test_batched_engine_matches_per_slot_tokens():
    (engb, toksb), (engs, tokss) = _run_both(SMOKE)
    assert toksb == tokss
    assert engb.decode_calls == engs.decode_calls
    assert engb.decode_batches < engb.decode_calls
    assert engs.decode_batches == engs.decode_calls
    # the pool drained with the traffic: every page recycled
    assert engb._pool is not None and engb._pool.pages_in_use == 0
    assert engb.stats()["decode_batches"] == engb.decode_batches


@pytest.mark.parametrize("mode", [EM.NON_STREAM, EM.LAYER_STREAM,
                                  EM.TILE_STREAM])
@pytest.mark.slow
def test_batched_engine_matches_per_slot_all_modes(mode):
    (_, toksb), (_, tokss) = _run_both(SMOKE, eng_kw={"mode": mode})
    assert toksb == tokss


@pytest.mark.slow
def test_batched_engine_sliding_window_ring_wrap():
    """Requests long enough to wrap the sliding-window ring buffer
    (kv > window=16) keep batched == per-slot."""
    (engb, toksb), (_, tokss) = _run_both(
        SLIDING, req_kw={"n": 4, "plen": (10, 14), "max_new": (10, 14),
                         "arrival_spread": 2})
    assert toksb == tokss
    assert engb.decode_batches < engb.decode_calls


def test_step_record_buckets_partition_decoded():
    """One bucket entry per step that decodes: every decoded rid, in the
    schedule's order, under the widest K/V length of the dispatch."""
    eng = Engine(SMOKE, _params(), slots=3, max_len=32)
    for r in _requests(SMOKE):
        eng.submit(r)
    eng.run()
    assert eng.decode_calls == sum(
        eng.last_schedule.decode_steps.values())
    for rec in eng.step_log:
        if not rec.decoded:
            assert rec.buckets is None
            continue
        ((kv, rids),) = rec.buckets
        assert rids == rec.decoded
        assert kv == max(rec.kv_lens)


@pytest.mark.parametrize("cfg", [SMOKE, SLIDING], ids=["full", "ring"])
def test_one_dispatch_per_decoding_step(cfg):
    """Ragged traffic (every slot at its own K/V length, the sliding
    config's ring wrapping at 16): one decode_step per step that decodes,
    the per-slot path's tokens, and ``decode_ragged`` counting the steps
    whose rows hold more than one length."""
    (engb, toksb), (_, tokss) = _run_both(
        cfg, req_kw={"n": 4, "plen": (3, 14), "max_new": (4, 14),
                     "arrival_spread": 2, "seed": 5})
    assert toksb == tokss
    decoding = [rec for rec in engb.step_log if rec.decoded]
    assert engb.decode_batches == len(decoding)
    ragged = sum(len(set(rec.kv_lens)) > 1 for rec in decoding)
    counters = engb.stats()["metrics"]["counters"]
    assert counters["decode_ragged"] == ragged > 0
    if cfg is SLIDING:                        # the ring wrapped
        assert max(max(rec.kv_lens) for rec in decoding) > 16


def test_batched_disabled_and_fallback_paths():
    # explicit opt-out records no buckets
    eng = Engine(SMOKE, _params(), slots=2, max_len=32,
                 batch_decode=False)
    for r in _requests(SMOKE, n=3):
        eng.submit(r)
    eng.run()
    assert all(rec.buckets is None for rec in eng.step_log)
    assert eng._pool is None
    # SSM cache trees can't page: auto-fallback, identical behaviour
    ssm = registry.get_config("mamba2-780m", smoke=True)
    (engb, toksb), (_, tokss) = _run_both(
        ssm, req_kw={"n": 3, "max_new": (2, 4)})
    assert toksb == tokss
    assert engb._pool is None
    assert all(rec.buckets is None for rec in engb.step_log)


# ---------------------------------------------------------------------------
# Coarse decode lowering == fine (satellite: sim equivalence)
# ---------------------------------------------------------------------------

TRAFFIC = [ServeRequest(0, 6, 5, 0), ServeRequest(1, 4, 3, 0),
           ServeRequest(2, 9, 4, 1), ServeRequest(3, 6, 6, 2),
           ServeRequest(4, 5, 2, 5)]


def _sim_pair(cfg=SMOKE, **kw):
    fine = simulate_serve(cfg, TRAFFIC, slots=3, **kw)
    coarse = simulate_serve(cfg, TRAFFIC, slots=3,
                            decode_lowering="coarse", **kw)
    return fine, coarse


def _assert_equivalent(fine, coarse):
    assert coarse.cycles == fine.cycles
    assert coarse.hbm_bytes == fine.hbm_bytes
    assert coarse.metrics == fine.metrics
    assert coarse.cycle_metrics == fine.cycle_metrics
    for a, b in zip(fine.steps, coarse.steps):
        assert a.to_dict() == b.to_dict()
    assert len(coarse.result.trace.events) < len(fine.result.trace.events)


def test_coarse_equals_fine_default_mode():
    _assert_equivalent(*_sim_pair())


@pytest.mark.parametrize("mode", [EM.NON_STREAM, EM.LAYER_STREAM,
                                  EM.TILE_STREAM])
@pytest.mark.slow
def test_coarse_equals_fine_forced_modes(mode):
    _assert_equivalent(*_sim_pair(mode=mode, force_mode=True))


def test_coarse_equals_fine_calibrated():
    """Per-resource cycle scaling applies once (in the memoized scratch
    run), never twice."""
    cal = {"ATTN": 1.7, "HBM": 1.3, "CIM": 2.0}
    _assert_equivalent(*_sim_pair(calibration=cal))


def test_coarse_cross_assert_still_fires():
    """The planner==simulator byte cross-assert survives coarsening: a
    decode plan predicting the wrong bytes still fails the run."""
    def bad_decode_plan(kv):
        dp = plan_decode_step(SMOKE, kv)
        lp = dp.layers[0]
        layers = (dataclasses.replace(lp, hbm_bytes=lp.hbm_bytes + 64),) \
            + dp.layers[1:]
        return dataclasses.replace(dp, layers=layers)
    with pytest.raises(RuntimeError, match="disagree on the decode"):
        simulate_serve(SMOKE, TRAFFIC, slots=3,
                       decode_plan_fn=bad_decode_plan,
                       decode_lowering="coarse")


def test_invalid_decode_lowering_rejected():
    with pytest.raises(ValueError, match="decode_lowering"):
        simulate_serve(SMOKE, TRAFFIC, slots=3, decode_lowering="medium")


@pytest.mark.slow
def test_coarse_event_reduction_long_context():
    """The point of coarsening: on long-context many-slot traffic the
    event count collapses (>= 2x here, growing with context x slots)
    while every reported number stays identical."""
    reqs = [ServeRequest(i, 48, 24, i % 4) for i in range(12)]
    fine = simulate_serve(SMOKE, reqs, slots=8)
    coarse = simulate_serve(SMOKE, reqs, slots=8,
                            decode_lowering="coarse")
    assert coarse.cycles == fine.cycles
    assert coarse.hbm_bytes == fine.hbm_bytes
    assert coarse.metrics == fine.metrics
    nf = len(fine.result.trace.events)
    nc = len(coarse.result.trace.events)
    assert nc * 2 <= nf, f"expected >=2x event reduction, got {nf}/{nc}"
