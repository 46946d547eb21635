"""Batched decode-attention kernel parity (DESIGN.md §15).

The batched path's correctness claim has two halves:

* batched-vs-B=1 within each implementation — a bucket row's online
  softmax never sees its neighbours, so slicing a row out of the batched
  call must reproduce the B=1 call, ragged lengths and sliding-window
  edges included.  The Pallas kernel runs one program per row, so the
  match is bitwise.  The ``jnp`` reference is compiled by XLA, which
  does not promise bit-identical fp32 reductions across batch sizes
  (XLA's CPU backend vectorizes a B=3 reduction differently from a B=1
  one), so its rows are held to ``JNP_ROW_TOL`` — a few fp32 ulps at
  the O(1) magnitudes of these outputs;
* *tolerance* across implementations — the batched kernels
  (``kernels.decode_attention`` Pallas, ``jnp_blocked`` reference)
  against the oracle ``ref_decode_attention`` and the per-slot
  ``decode_attention_by_plan`` path (different reduction blocking ⇒
  last-ulp differences), across all three execution modes.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.types import ExecutionMode as EM
from repro.kernels.decode_attention import decode_attention
from repro.kernels.jnp_blocked import decode_attention_jnp
from repro.kernels.ops import (batched_decode_attention_by_plan,
                               decode_attention_by_plan,
                               multi_head_attention)
from repro.kernels.ref import ref_decode_attention
from repro.plan import plan_decode_step

SMOKE = registry.get_config("starcoder2-7b", smoke=True)
MODES = [EM.NON_STREAM, EM.LAYER_STREAM, EM.TILE_STREAM]


def _inputs(B=3, Hq=4, Hkv=2, W=48, hd=16, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, W, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, W, hd)), dtype)
    return q, k, v


RAGGED = jnp.asarray([17, 48, 5], jnp.int32)      # mid / full / tiny
JNP_ROW_TOL = 1e-6


def _assert_row_matches(impl, row, solo, msg=""):
    """Pallas rows are bitwise; jnp rows within ``JNP_ROW_TOL`` (see the
    module docstring for why XLA's fp32 reductions are not bitwise)."""
    if impl == "pallas":
        assert jnp.array_equal(row, solo), msg
    else:
        np.testing.assert_allclose(np.asarray(row), np.asarray(solo),
                                   rtol=JNP_ROW_TOL, atol=JNP_ROW_TOL,
                                   err_msg=msg)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_batched_equals_per_slot_bitwise_fp32(impl):
    """fp32 bucket rows match B=1 calls of the same implementation, per
    ragged row length: bit-identical for Pallas, within ``JNP_ROW_TOL``
    for the XLA-compiled jnp reference."""
    q, k, v = _inputs()
    fn = (decode_attention_jnp if impl == "jnp"
          else lambda *a, **kw: decode_attention(*a, interpret=True, **kw))
    batched = fn(q, k, v, RAGGED)
    for i in range(q.shape[0]):
        solo = fn(q[i:i + 1], k[i:i + 1], v[i:i + 1], RAGGED[i])
        _assert_row_matches(
            impl, batched[i:i + 1], solo,
            f"{impl}: row {i} (len {int(RAGGED[i])}) differs from B=1")


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_batched_matches_oracle(impl):
    q, k, v = _inputs()
    fn = (decode_attention_jnp if impl == "jnp"
          else lambda *a, **kw: decode_attention(*a, interpret=True, **kw))
    out = fn(q, k, v, RAGGED)
    ref = ref_decode_attention(q, k, v, RAGGED)
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("window", [1, 4, 5, 17, 48, 64])
def test_sliding_window_edges(impl, window):
    """Window edges (1, == tiny row's len, around each len, > W) match
    the oracle and keep batched rows equal to B=1 calls (bitwise for
    Pallas, within ``JNP_ROW_TOL`` for jnp)."""
    q, k, v = _inputs()
    fn = (decode_attention_jnp if impl == "jnp"
          else lambda *a, **kw: decode_attention(*a, interpret=True, **kw))
    out = fn(q, k, v, RAGGED, window=window)
    ref = ref_decode_attention(q, k, v, RAGGED, window=window)
    assert jnp.max(jnp.abs(out - ref)) < 1e-5
    for i in range(q.shape[0]):
        solo = fn(q[i:i + 1], k[i:i + 1], v[i:i + 1], RAGGED[i],
                  window=window)
        _assert_row_matches(impl, out[i:i + 1], solo)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_bf16_batched_within_tolerance(impl):
    """bf16 buckets match B=1 bitwise (same-impl) and the fp32 oracle
    within bf16 resolution."""
    q, k, v = _inputs(dtype=jnp.bfloat16)
    fn = (decode_attention_jnp if impl == "jnp"
          else lambda *a, **kw: decode_attention(*a, interpret=True, **kw))
    out = fn(q, k, v, RAGGED)
    for i in range(q.shape[0]):
        solo = fn(q[i:i + 1], k[i:i + 1], v[i:i + 1], RAGGED[i])
        assert jnp.array_equal(out[i:i + 1], solo)
    ref = ref_decode_attention(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32), RAGGED)
    assert jnp.max(jnp.abs(out.astype(jnp.float32) - ref)) < 3e-2


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("use_pallas", [False, True])
def test_by_plan_batched_vs_per_slot_path(mode, use_pallas):
    """The plan-dispatched batched entry agrees with the existing
    per-slot ``decode_attention_by_plan`` row-for-row across all three
    modes and a ragged shape bucket (fp32; different reduction blocking
    bounds the comparison at ~1 ulp of the softmax sum)."""
    lens = tuple(int(c) for c in RAGGED)
    dp = plan_decode_step(SMOKE, lens, mode=mode, force_mode=True)
    lp = dp.layers[0]
    hd = lp.head_dim
    q, k, v = _inputs(B=len(lens), Hq=lp.heads, Hkv=lp.kv_heads,
                      W=max(lens), hd=hd)
    batched = batched_decode_attention_by_plan(
        lp, q, k, v, jnp.asarray(lens, jnp.int32), use_pallas=use_pallas)
    for i, c in enumerate(lens):
        solo = decode_attention_by_plan(
            lp, q[i:i + 1], k[i:i + 1, :, :c], v[i:i + 1, :, :c])
        assert jnp.max(jnp.abs(batched[i:i + 1] - solo)) < 1e-6, (
            f"mode {mode}: row {i} diverges from decode_attention_by_plan")


def test_by_plan_rejects_mismatched_bucket():
    dp = plan_decode_step(SMOKE, (9, 9), force_mode=False)
    lp = dp.layers[0]
    q, k, v = _inputs(B=3, Hq=lp.heads, Hkv=lp.kv_heads, W=16,
                      hd=lp.head_dim)
    from repro.sim.replay import KernelRecorder, recording
    with recording(KernelRecorder()):
        with pytest.raises(ValueError, match="bucket batch"):
            batched_decode_attention_by_plan(
                lp, q, k, v, jnp.asarray([9, 9, 9], jnp.int32))


def test_by_plan_recorder_sums_per_slot_bytes():
    """A recorded bucket op charges the sum of the plan's per-slot
    attended bytes — the same total B x B=1 recordings would charge — so
    replayed batched traces keep the sim cross-assert exact."""
    from repro.plan.heuristics import decode_attn_hbm_bytes
    from repro.sim.replay import KernelRecorder, recording
    lens = (17, 48, 5)
    dp = plan_decode_step(SMOKE, lens)
    lp = dp.layers[0]
    q, k, v = _inputs(B=3, Hq=lp.heads, Hkv=lp.kv_heads, W=48,
                      hd=lp.head_dim)
    rec = KernelRecorder()
    with recording(rec):
        batched_decode_attention_by_plan(
            lp, q, k, v, jnp.asarray(lens, jnp.int32))
    (kt,) = rec.records
    expect = sum(decode_attn_hbm_bytes(
        kv, lp.heads, lp.kv_heads, lp.head_dim, lp.mode,
        append=not lp.cross, bytes_per_el=4) for kv in lp.seq_kv)
    assert kt.kind == "decode"
    assert kt.hbm_bytes == expect
    assert kt.op == lp.name


def test_full_width_matches_multi_head_attention():
    """A full bucket (every row attends the whole buffer) reduces to
    plain single-query MHA."""
    q, k, v = _inputs()
    W = k.shape[2]
    out = decode_attention_jnp(q, k, v, W)
    mh = multi_head_attention(q, k, v, causal=False, block_q=8,
                              block_k=256)
    assert jnp.max(jnp.abs(out - mh)) < 1e-6


@pytest.mark.parametrize("name,lens", [
    ("starcoder2-7b", (3, 17, 30)),            # full attention, W = 32
    ("h2o-danube3-4b", (3, 17, 30))],          # ring of W = 16: two wrap
    ids=["full", "ring"])
def test_decode_step_per_row_positions(name, lens):
    """``decode_step`` with a ``(B,)`` ``len`` of unequal positions: each
    row's logits and new cache row equal, bitwise, that row of the same
    batch decoded at its scalar position, and a B=1 ``decode_step`` at
    that position gives the row's token, with logits within
    ``JNP_ROW_TOL`` of the logit scale (XLA's fp32 reductions depend on
    the batch size; see the module docstring)."""
    import jax

    cfg = registry.get_config(name, smoke=True)
    mod = registry.model_module(cfg)
    params = mod.init(jax.random.PRNGKey(0), cfg)
    step = jax.jit(lambda c, t: mod.decode_step(params, cfg, c, t))
    B = len(lens)
    shape = mod.init_cache(cfg, B, 32)["layers"]["k"].shape
    rng = np.random.default_rng(0)
    layers = {side: jnp.asarray(rng.normal(size=shape), jnp.float32)
              for side in ("k", "v")}
    toks = jnp.asarray([[5], [7], [9]], jnp.int32)
    logits, new = step({"layers": layers,
                        "len": jnp.asarray(lens, jnp.int32)}, toks)
    assert new["len"].tolist() == [n + 1 for n in lens]
    for i, n in enumerate(lens):
        pos = jnp.asarray(n, jnp.int32)
        same, same_c = step({"layers": layers, "len": pos}, toks)
        assert jnp.array_equal(logits[i], same[i]), f"row {i}"
        for side in ("k", "v"):
            assert jnp.array_equal(new["layers"][side][:, i],
                                   same_c["layers"][side][:, i])
        row = jax.tree.map(lambda a: a[:, i:i + 1], layers)
        solo, _ = step({"layers": row, "len": pos}, toks[i:i + 1])
        assert int(jnp.argmax(solo[0, 0])) == int(jnp.argmax(logits[i, 0]))
        scale = float(jnp.max(jnp.abs(solo)))
        np.testing.assert_allclose(np.asarray(logits[i:i + 1]),
                                   np.asarray(solo), rtol=0,
                                   atol=JNP_ROW_TOL * scale)
