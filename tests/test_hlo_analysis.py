"""Validation of the while-trip-aware HLO analyzer (launch/hlo_analysis.py)
— the §Roofline methodology.  Ground truths are hand-computed FLOPs."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as HA


def _analyze(fn, *args, devices=1):
    comp = jax.jit(fn).lower(*args).compile()
    return HA.analyze(comp.as_text(), total_devices=devices,
                      multi_pod=False)


def test_plain_matmul_chain_exact():
    a = jnp.zeros((256, 512))
    b = jnp.zeros((512, 128))
    c = jnp.zeros((128, 64))
    r = _analyze(lambda a, b, c: (a @ b) @ c, a, b, c)
    assert r["flops"] == 2 * 256 * 512 * 128 + 2 * 256 * 128 * 64


def test_scan_multiplies_by_trip_count():
    def g(x, w):
        def step(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(step, x, None, length=10)
        return y

    x = jnp.zeros((128, 256))
    w = jnp.zeros((256, 256))
    r = _analyze(g, x, w)
    assert r["flops"] == 10 * 2 * 128 * 256 * 256


def test_scan_remat_microbatch_exact():
    """The exact structure of a train step: mb scan over value_and_grad of
    a rematted layer scan.  fwd + remat-fwd + dx + dw = 4 matmul passes."""
    L, B, S, D, MB = 4, 8, 32, 64, 2

    def layer(x, w):
        return jnp.tanh(x @ w)

    def loss(ws, xb):
        def step(c, w):
            return jax.checkpoint(layer)(c, w), None
        y, _ = jax.lax.scan(step, xb, ws)
        return jnp.mean(y ** 2)

    def train(ws, xs):
        def mb_step(acc, xb):
            l, g = jax.value_and_grad(loss)(ws, xb)
            return jax.tree.map(jnp.add, acc, g), l
        g0 = jax.tree.map(jnp.zeros_like, ws)
        g, ls = jax.lax.scan(mb_step, g0, xs)
        return g, ls.mean()

    ws = jnp.zeros((L, D, D))
    xs = jnp.zeros((MB, B, S, D))
    r = _analyze(train, ws, xs)
    expect = MB * L * (2 * B * S * D * D) * 4
    assert abs(r["flops"] - expect) / expect < 1e-6
    # XLA's own cost analysis must be a large undercount here (the reason
    # this analyzer exists)
    ca = jax.jit(train).lower(ws, xs).compile().cost_analysis()
    assert ca["flops"] < 0.3 * expect


def test_scanned_equals_unrolled_model():
    """Same computation scanned vs python-unrolled must analyze equal."""
    from repro.core import runtime

    def layer(x, w):
        return jnp.tanh(x @ w)

    def f_scan(x, ws):
        def step(c, w):
            return layer(c, w), None
        y, _ = jax.lax.scan(step, x, ws)
        return jnp.sum(y)

    def f_unrolled(x, ws):
        c = x
        for i in range(ws.shape[0]):
            c = layer(c, ws[i])
        return jnp.sum(c)

    x = jnp.zeros((64, 128))
    ws = jnp.zeros((6, 128, 128))
    r1 = _analyze(f_scan, x, ws)
    r2 = _analyze(f_unrolled, x, ws)
    assert r1["flops"] == r2["flops"]


def test_sharded_collective_traffic_exact():
    import os
    if jax.device_count() < 2:
        pytest.skip("needs >1 host device (dry-run only)")


def test_collective_formulas():
    """Ring-traffic arithmetic on synthetic HLO lines."""
    hlo = """
HloModule m, entry_computation_layout={()->f32[]}

ENTRY %main (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ar = f32[16,16]{1,0} all-reduce(%p), replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
  ROOT %ag = f32[16,16]{1,0} all-gather(%ar), replica_groups=[4,2]<=[8], dimensions={0}
}
"""
    r = HA.analyze(hlo, total_devices=8, multi_pod=False)
    size = 16 * 16 * 4
    # all-reduce group 4: 2*s*(3/4); all-gather group 2: s*(1/2)
    assert abs(r["ici"] - (2 * size * 3 / 4 + size / 2)) < 1e-6
    assert r["counts"] == {"all-reduce": 1, "all-gather": 1}
