"""Main-path programs compiled for a described TPU v5e (no chip attached).

The TPU compiler is installed even where no chip is: it compiles for a
``v5e:2x2`` topology that is only described, and refuses what the chip
would refuse (block shapes off the (8, 128) tiling, VMEM overruns,
programs that do not fit HBM).  Nothing runs, so these tests say nothing
about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test
worker imports every test file.  The persistent compile cache is off
while these compile (an entry written for a described chip cannot be
read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.tile_gemm import tile_gemm

MINITRON = registry.get_config("minitron-4b")
HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:              # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_compiles_natively(one_chip):
    m = MINITRON
    q = _shape(one_chip, (1, m.num_heads, 1024, m.head_dim))
    kv = _shape(one_chip, (1, m.num_kv_heads, 1024, m.head_dim))
    c = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 interpret=False), q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_tile_gemm_compiles_natively(one_chip):
    x = _shape(one_chip, (1024, MINITRON.d_model))
    w = _shape(one_chip, (MINITRON.d_model, MINITRON.d_ff))
    c = _compile(lambda x, w: tile_gemm(x, w, interpret=False), x, w)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("batch", [1, 4])
def test_decode_attention_compiles_natively(one_chip, batch):
    """B > 1 was refused while per-row lengths rode in a (1, 128) VMEM
    block; they are a scalar-prefetch operand now."""
    m = MINITRON
    q = _shape(one_chip, (batch, m.num_heads, 1, m.head_dim))
    kv = _shape(one_chip, (batch, m.num_kv_heads, 2048, m.head_dim))
    lens = _shape(one_chip, (batch,), jnp.int32)
    c = _compile(lambda q, k, v, n: decode_attention(q, k, v, n,
                                                     interpret=False),
                 q, kv, kv, lens)
    assert "tpu_custom_call" in c.as_text()


def test_minitron_decode_step_fits_one_chip(one_chip):
    """minitron-4b's serving step at 4 slots x 2048 positions: parameters,
    cache, new cache and temporaries within one chip's HBM."""
    cfg = MINITRON
    mod = registry.model_module(cfg)
    on_chip = lambda tree: jax.tree.map(                      # noqa: E731
        lambda a: _shape(one_chip, a.shape, a.dtype), tree)
    params = on_chip(registry.param_specs(cfg))
    cache = on_chip(jax.eval_shape(lambda: mod.init_cache(cfg, 4, 2048)))
    toks = _shape(one_chip, (4, 1), jnp.int32)
    c = _compile(lambda p, c, t: mod.decode_step(p, cfg, c, t),
                 params, cache, toks)
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"
