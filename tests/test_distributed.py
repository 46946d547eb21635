"""Sharding rules, grouped-MoE dispatch, and compression unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import runtime
from repro.core.types import Family, ModelConfig
from repro.distributed import sharding as SH
from repro.models import layers as L


# Production axis sizes, simulated for rule evaluation: the test mesh is
# single-device, and the rules read only axis sizes.
PROD_SIZES = {"data": 16, "model": 16, "pod": 2}


@pytest.mark.parametrize("arch", list(registry.ARCHS))
def test_param_shardings_cover_every_leaf(arch):
    """Every param leaf gets a sharding whose partitioned dims divide."""
    cfg = registry.get_config(arch)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    pspecs = registry.param_specs(cfg)
    shardings = SH.param_shardings(pspecs, cfg, mesh,
                                   axis_sizes=PROD_SIZES)
    flat_p = jax.tree.leaves(pspecs)
    flat_s = jax.tree.leaves(shardings, is_leaf=lambda x: isinstance(
        x, jax.sharding.NamedSharding))
    assert len(flat_p) == len(flat_s)
    sizes = PROD_SIZES
    for p, s in zip(flat_p, flat_s):
        spec = s.spec
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axs = ax if isinstance(ax, tuple) else (ax,)
            factor = 1
            for a in axs:
                factor *= sizes[a]
            assert p.shape[dim] % factor == 0, (arch, p.shape, spec, dim)


def test_head_sharding_rules():
    # qwen3: 64 heads % 16 ok -> head-sharded; starcoder2: 36 heads -> not
    q3 = registry.get_config("qwen3-32b")
    sc = registry.get_config("starcoder2-7b")

    class M:  # mesh stub with production sizes
        shape = PROD_SIZES
    assert SH.heads_shardable(q3, M)
    assert not SH.heads_shardable(sc, M)
    assert SH.experts_shardable(registry.get_config("deepseek-v3-671b"), M)
    assert not SH.experts_shardable(registry.get_config("grok-1-314b"), M)


def _specs_by_path(arch, **kwargs):
    """path -> PartitionSpec for every param leaf, rules evaluated at
    production axis sizes on the single-device test mesh."""
    cfg = registry.get_config(arch)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    shardings = SH.param_shardings(registry.param_specs(cfg), cfg, mesh,
                                   axis_sizes=PROD_SIZES, **kwargs)
    flat, _ = SH._flatten_with_paths(shardings)
    return dict(flat)


def test_megatron_head_split_when_divisible():
    """64 heads % 16 == 0: attention projections shard their head dim
    over 'model' (col-parallel qkv, row-parallel o).  FSDP is pushed out
    of the way (qwen3-32b is over the default threshold) to see the pure
    Megatron rule."""
    specs = {p: s.spec
             for p, s in _specs_by_path("qwen3-32b",
                                        fsdp_threshold=1e15).items()}
    wq = [s for p, s in specs.items() if p.endswith("/wq")]
    wo = [s for p, s in specs.items() if p.endswith("/wo")]
    # Stacked layer dim replicated; head dim (middle of D,H,hd) sharded.
    assert wq and all(tuple(s) == (None, None, "model", None) for s in wq)
    assert wo and all(tuple(s) == (None, "model", None, None) for s in wo)


@pytest.mark.parametrize("arch", ["starcoder2-7b", "qwen2-vl-2b"])
def test_context_parallel_fallback_replicates_attention(arch):
    """Non-divisible heads (36H, 12H/2KV vs |model|=16): qkv/o weights
    stay replicated (attention runs context-parallel instead) while the
    MLP keeps its tensor split."""
    specs = {p: s.spec for p, s in _specs_by_path(arch).items()}
    attn = {p: s for p, s in specs.items()
            if p.split("/")[-1] in ("wq", "wk", "wv", "wo")}
    assert attn
    assert all(all(ax is None for ax in tuple(s)) for s in attn.values()), \
        {p: tuple(s) for p, s in attn.items()}
    ups = [s for p, s in specs.items() if p.endswith("/w_up")]
    assert ups and all("model" in tuple(s) for s in ups)


def test_fsdp_threshold_gates_data_axis():
    """starcoder2 (~7e9 params) sits under the default 8e9 threshold —
    no 'data' factor anywhere; forcing the threshold to 0 turns ZeRO-3
    sharding on for its replicated attention weights."""
    def data_sharded(specs):
        return [p for p, s in specs.items()
                if any(ax == "data" for ax in tuple(s.spec))]
    off = _specs_by_path("starcoder2-7b")
    assert not data_sharded(off)
    on = _specs_by_path("starcoder2-7b", fsdp_threshold=0)
    hit = data_sharded(on)
    assert any(p.split("/")[-1] in ("wq", "wk", "wv", "wo") for p in hit), hit


def test_grouped_moe_matches_plain():
    cfg = ModelConfig(name="t", family=Family.MOE, num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      num_experts=4, experts_per_token=2, moe_d_ff=96,
                      dtype="float32", param_dtype="float32")
    p = L.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64)) * 0.5
    with runtime.flags(moe_capacity=100.0):
        y1 = L.moe_forward(p, cfg, x)
        with runtime.flags(moe_groups=4):
            y4 = L.moe_forward(p, cfg, x)
    np.testing.assert_allclose(y1, y4, atol=2e-5, rtol=2e-5)


def test_hints_noop_without_table():
    from repro.distributed.hints import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, "attn_q") is x


def test_quantize_roundtrip_error_bounded():
    from repro.distributed.compression import _dequantize, _quantize
    g = jax.random.normal(jax.random.PRNGKey(0), (128, 128)) * 0.02
    q, s = _quantize(g)
    err = jnp.abs(_dequantize(q, s) - g).max()
    assert float(err) <= float(s) / 2 + 1e-9   # half-ulp of the int8 grid
