"""Tracing inside the serving loop: the K/V pool's jitted copies give
bitwise what the eager arithmetic they replace gave, every device program
on the serving path lowers under a stable name, and the engine counts its
host reads, admissions and decode dispatches per ``run``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.serve.engine import Engine, Request
from repro.serve.kv_cache import kv_gather, kv_scatter, kv_write

SMOKE = registry.get_config("starcoder2-7b", smoke=True)


# The eager arithmetic of the pool before its copies were jitted, kept
# here as the reference.
def _eager_gather(pool, ids, width):
    B, npg = ids.shape
    _, L, Hkv, pg, hd = pool.shape
    pages = jnp.take(pool, ids.reshape(-1), axis=0)
    pages = pages.reshape(B, npg, L, Hkv, pg, hd)
    dense = jnp.moveaxis(pages, 1, 3)
    dense = dense.reshape(B, L, Hkv, npg * pg, hd)
    dense = jnp.moveaxis(dense, 0, 1)
    S = npg * pg
    if S < width:
        dense = jnp.pad(dense, ((0, 0), (0, 0), (0, 0), (0, width - S),
                                (0, 0)))
    return dense[:, :, :, :width]


def _eager_scatter(pool, ids, dense, width):
    B, npg = ids.shape
    _, L, Hkv, pg, hd = pool.shape
    S = npg * pg
    dense = jnp.moveaxis(dense, 1, 0)
    if S > width:
        dense = jnp.pad(dense, ((0, 0), (0, 0), (0, 0), (0, S - width),
                                (0, 0)))
    pages = dense[:, :, :, :S].reshape(B, L, Hkv, npg, pg, hd)
    pages = jnp.moveaxis(pages, 3, 1)
    return pool.at[ids.reshape(-1)].set(
        pages.reshape(B * npg, L, Hkv, pg, hd))


def _eager_write(pool, ids, dense, width):
    (npg,) = ids.shape
    _, L, Hkv, pg, hd = pool.shape
    S = npg * pg
    k = dense[:, 0]
    if S > width:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, S - width), (0, 0)))
    pages = k[:, :, :S].reshape(L, Hkv, npg, pg, hd)
    return pool.at[ids].set(jnp.moveaxis(pages, 2, 0))


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))


# (B, npg, width): pages short of the width, exactly on it, and past it
# (a width that is not a multiple of the page size).
SHAPES = [(1, 1, 24), (2, 2, 24), (3, 3, 24), (2, 3, 20), (1, 2, 16)]


@pytest.mark.parametrize("B,npg,width", SHAPES)
def test_kv_programs_bitwise_equal_to_eager(B, npg, width):
    L, Hkv, pg, hd, pages = 2, 2, 8, 4, 12
    rng = np.random.default_rng(B * 10 + npg)
    pool = jnp.asarray(rng.normal(size=(pages, L, Hkv, pg, hd)),
                       jnp.bfloat16)
    ids = rng.permutation(pages)[:B * npg].astype(np.int32).reshape(B, npg)
    dense = jnp.asarray(rng.normal(size=(L, B, Hkv, width, hd)),
                        jnp.bfloat16)
    np.testing.assert_array_equal(_bits(kv_gather(pool, ids, width)),
                                  _bits(_eager_gather(pool, ids, width)))
    np.testing.assert_array_equal(
        _bits(kv_scatter(pool, ids, dense)),
        _bits(_eager_scatter(pool, ids, dense, width)))
    np.testing.assert_array_equal(
        _bits(kv_write(pool, ids[0], dense[:, :1])),
        _bits(_eager_write(pool, ids[0], dense[:, :1], width)))


def _module_name(lowered) -> str:
    return lowered.as_text().split("module @", 1)[1].split(" ", 1)[0]


def test_device_programs_have_stable_names():
    pool = jnp.zeros((6, 2, 2, 8, 4), jnp.bfloat16)
    ids = np.zeros((2, 2), np.int32)
    dense = jnp.zeros((2, 2, 2, 24, 4), jnp.bfloat16)
    assert _module_name(kv_gather.lower(pool, ids, 24)) == "jit_kv_gather"
    assert _module_name(kv_scatter.lower(pool, ids, dense)) \
        == "jit_kv_scatter"
    assert _module_name(kv_write.lower(pool, ids[0], dense[:, :1])) \
        == "jit_kv_write"
    mod = registry.model_module(SMOKE)
    params = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), SMOKE))
    eng = Engine(SMOKE, params, slots=2, max_len=32)
    cache = jax.eval_shape(lambda: mod.init_cache(SMOKE, 2, 32))
    toks = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    assert _module_name(eng._decode.lower(params, cache, toks)) \
        == "jit_decode_step"


def _requests(n=5, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, SMOKE.vocab_size,
                                        size=(int(rng.integers(3, 10)),)
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 6)),
                    arrival_step=int(rng.integers(0, 3)))
            for i in range(n)]


@pytest.fixture(scope="module")
def params():
    return registry.model_module(SMOKE).init(jax.random.PRNGKey(0), SMOKE)


@pytest.mark.parametrize("batch", [True, False])
def test_counters_count_every_host_read(params, batch):
    """Batched: token #1 and the pool's length read per admission, the
    scatter's length read and the bucket's tokens per decode dispatch.
    Per slot: token #1 per admission, the token per decode dispatch."""
    eng = Engine(SMOKE, params, slots=3, max_len=32, batch_decode=batch)
    for _ in range(2):                       # counters reset per run
        reqs = _requests()
        for r in reqs:
            eng.submit(r)
        eng.run()
        c = eng.stats()["metrics"]["counters"]
        admissions = sum(len(rec.admitted) for rec in eng.step_log)
        assert admissions == len(reqs) == c["admissions"]
        assert c["decode_batches"] == eng.decode_batches > 0
        assert c["steps"] == len(eng.step_log)
        per_admit, per_batch = (2, 2) if batch else (1, 1)
        assert c["host_syncs"] == (per_admit * admissions
                                   + per_batch * eng.decode_batches)
