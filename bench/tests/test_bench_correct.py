"""``correct`` on a tiny configuration on the CPU: a sound run passes; the
control (the reference computed through float8, the precision below the
configuration's bfloat16) and each fault planted in the timed path fail.

Readings at this size on the CPU over seeds 0-3 (served_logit_gap, the
widest gap of a served token below the float32 reference's best logit),
``tiny.offline``: the program 0 on every seed, the float8 control 0.078,
0.057, 0.038 and 0 (on seed 3 float8 ranks every token as float32 does).
The limit here, 0.03, lies between the program and the control on seed
0, the seed these tests run.  The cells' own limits are set from chip
runs at their sizes (PERF.md)."""
import functools
import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny

LIMIT = 0.03
SEED = 0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"), gap_limit=LIMIT)


def _run(root, cell_name="tiny.offline", seconds=0.3):
    cell = harness.load_cell(cell_name, root)
    return harness.run_cell(cell, seed=SEED, seconds=seconds, trace=False,
                            t_start=time.perf_counter())


@pytest.mark.parametrize("cell", ["tiny.offline", "tiny.open",
                                  "tiny.lockstep"])
def test_sound_run_is_correct(root, cell):
    res = _run(root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s"}
    assert res["checks"]["served_logit_gap"]["limit"] == LIMIT


def test_control_fails(root):
    """The float8 reference in the program's place, judged by the run's own
    comparison, comes out as not correct; the program's reading of the same
    window stays under the limit."""
    cell = harness.load_cell("tiny.offline", root)
    res = harness.run_cell(cell, seed=SEED, seconds=0.3, trace=False,
                           t_start=time.perf_counter(), control=True)
    assert not res["correct"]
    assert res["checks"]["served_logit_gap"]["value"] > LIMIT
    assert res["program_gap"] <= LIMIT


def _roll(fn):
    """``fn`` with its logits shifted by one token: the token it produces
    is altered where it is produced."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        logits, cache = fn(*args, **kwargs)
        return jnp.roll(logits, 1, axis=-1), cache
    return wrapped


def _half_batch(fn):
    """A decode bucket computes only its first half: every later row gets
    the logits of a row of that half."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        logits, cache = fn(*args, **kwargs)
        b = logits.shape[0]
        h = (b + 1) // 2
        return jnp.concatenate([logits[:h], logits[:b - h]], axis=0), cache
    return wrapped


def _stale_scatter(orig):
    """The pool keeps its state unchanged across a decode step."""
    def scatter(self, slot_ids, cache):
        k, v = self._k_pool, self._v_pool
        orig(self, slot_ids, cache)
        self._k_pool, self._v_pool = k, v
    return scatter


@pytest.mark.parametrize("fault", ["decode_token", "prefill_token",
                                   "stale_kv", "half_batch"])
def test_fault_fails(root, monkeypatch, fault):
    from repro.models import transformer
    from repro.serve.kv_cache import PagedKVCache
    if fault == "decode_token":
        monkeypatch.setattr(transformer, "decode_step",
                            _roll(transformer.decode_step))
    elif fault == "half_batch":
        monkeypatch.setattr(transformer, "decode_step",
                            _half_batch(transformer.decode_step))
    elif fault == "prefill_token":
        monkeypatch.setattr(transformer, "prefill",
                            _roll(transformer.prefill))
    else:
        monkeypatch.setattr(PagedKVCache, "scatter",
                            _stale_scatter(PagedKVCache.scatter))
    res = _run(root, "tiny.lockstep" if fault == "half_batch"
               else "tiny.offline")
    assert not res["correct"]
    assert res["checks"]["served_logit_gap"]["value"] > LIMIT
