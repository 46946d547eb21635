"""FLOP and byte counts behind ``serve_mfu`` and ``decode_hbm_share``:
against the program's parameter count, hand-worked shapes, and the work
XLA's own cost analysis finds in the program's step (the counts are the
least a step must do, so they can never exceed it)."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import counts, harness
from bench.tests import tiny

CONFIGS = ("minitron-4b", "starcoder2-7b-l16")


def _sizes(name):
    return json.loads((harness.ROOT / "bench" / "configs"
                       / f"{name}.json").read_text())["model"]


def _cfg(sizes, name="t"):
    from repro.core.types import Family, ModelConfig
    m = dict(sizes, family=Family(sizes["family"]))
    return ModelConfig(name=name, **m)


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_match_param_count(name):
    s = _sizes(name)
    cfg = _cfg(s)
    v, d, L = s["vocab_size"], s["d_model"], s["num_layers"]
    # param_count = embed + unembed + layers (with two norm gains each)
    assert counts.linear_params(s) + 2 * L * d + 2 * v * d == \
        cfg.param_count()
    want = (cfg.param_count() - v * d + d) * 2     # bf16, no embed table
    assert counts.weight_bytes_per_step(s) == want


def test_hand_worked_minitron():
    s = _sizes("minitron-4b")
    per_layer = 3072 * 128 * (24 + 16) + 24 * 128 * 3072 + 2 * 3072 * 9216
    assert counts.layer_linear_params(s) == per_layer == 81_788_928
    assert counts.kv_bytes_per_position(s) == 2 * 32 * 8 * 128 * 2 == 131072
    assert counts.decode_flops(s, 100) == (2 * 32 * per_layer
                                           + 4 * 32 * 24 * 128 * 100
                                           + 2 * 3072 * 256000)
    assert counts.prefill_flops(s, 4) == (2 * 32 * per_layer * 4
                                          + 4 * 32 * 24 * 128 * 10
                                          + 2 * 3072 * 256000)


def test_hand_worked_starcoder2_stage():
    s = _sizes("starcoder2-7b-l16")
    per_layer = 4608 * 128 * (36 + 8) + 36 * 128 * 4608 + 2 * 4608 * 18432
    assert counts.layer_linear_params(s) == per_layer
    assert counts.kv_bytes_per_position(s) == 2 * 16 * 4 * 128 * 2 == 32768
    total = counts.linear_params(s) + 2 * 4608 * 49152 + 2 * 16 * 4608
    assert 3.9e9 < total < 3.95e9
    step = counts.decode_step_bytes(s, [10, 20])
    assert step == (counts.weight_bytes_per_step(s) + 2 * 4608 * 2
                    + (9 + 19) * 32768 + 2 * 32768)
    assert counts.request_flops(s, 8, 3) == (
        counts.prefill_flops(s, 8) + counts.decode_flops(s, 9)
        + counts.decode_flops(s, 10))


def _cost(compiled):
    c = compiled.cost_analysis()
    return c["flops"], c["bytes accessed"]


def test_counts_never_exceed_what_the_program_does():
    """On a tiny configuration, XLA's cost analysis of the program's own
    decode step and prefill finds at least the counted FLOPs and bytes.
    One layer, because the analysis counts a scanned body once."""
    from repro.models import transformer
    s = dict(tiny.TINY_MODEL, num_layers=1, d_model=128, d_ff=256,
             vocab_size=512)
    cfg = _cfg(s)
    params = jax.eval_shape(lambda: transformer.init(
        jax.random.PRNGKey(0), cfg))
    B, W, kv = 3, 256, 200
    cache = jax.eval_shape(lambda: transformer.init_cache(cfg, B, W))
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    step = jax.jit(lambda p, c, t: transformer.decode_step(p, cfg, c, t))
    flops, nbytes = _cost(step.lower(params, cache, toks).compile())
    assert B * counts.decode_flops(s, kv) <= flops
    assert counts.decode_step_bytes(s, [kv] * B) <= nbytes
    S = 64
    pre = jax.jit(lambda p, t: transformer.prefill(p, cfg, {"tokens": t},
                                                   max_len=W))
    flops, _ = _cost(pre.lower(params, jax.ShapeDtypeStruct(
        (1, S), jnp.int32)).compile())
    assert counts.prefill_flops(s, S) <= flops
