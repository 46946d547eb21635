"""The seeded traffic generator: one seed gives one trace, seeds differ
only in token ids, every length is on the mix's grid, and every
request fits the engine of each cell that uses the mix."""
import json

import numpy as np
import pytest

from bench import harness
from bench import traffic as gen
from bench.tests import tiny

MIXES = {"chat": json.loads((harness.ROOT / "bench" / "traffic"
                             / "chat.json").read_text()),
         "open": tiny.TINY_MIXES["tiny-open"]}
SEED = 2**31 + 977          # larger than a signed 32-bit integer


def _trace(mix, seed, seconds=30.0, slots=4, vocab=1000):
    if mix["kind"] == "offline":
        reqs = [r for w in range(3)
                for r in gen.wave(mix, seed, w, slots, vocab)]
    else:
        reqs = gen.open_loop(mix, seed, seconds, vocab)
    return [(r.prompt.tolist(), r.max_new_tokens, r.due_s) for r in reqs]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_trace(name):
    assert _trace(MIXES[name], SEED) == _trace(MIXES[name], SEED)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_seeds_differ_only_in_order_and_ids(name):
    """Since the order of lengths decides which requests share a decode
    step, it is fixed too: two seeds give the same lengths and arrival
    times in the same order, and differ in token ids alone."""
    a, b = _trace(MIXES[name], SEED), _trace(MIXES[name], 5)
    assert a != b
    assert ([(len(p), o) for p, o, _ in a]
            == [(len(p), o) for p, o, _ in b])
    assert [t for _, _, t in a] == pytest.approx([t for _, _, t in b])
    assert all(pa != pb for (pa, _, _), (pb, _, _) in zip(a, b))
    if MIXES[name]["kind"] == "offline":   # every wave the same lengths
        n = len(a) // 3
        assert len({tuple((len(p), o) for p, o, _ in a[w * n:(w + 1) * n])
                    for w in range(3)}) == 1
        assert len({tuple(p[:4]) for p, _, _ in a}) == len(a)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_lengths_on_grid_and_in_range(name):
    mix = MIXES[name]
    for prompt, out, _ in _trace(mix, SEED):
        assert len(prompt) in mix["prompt"]["grid"]
        assert mix["output"]["min"] <= out <= mix["output"]["max"]


def test_every_cell_fits_its_engine():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        max_len = cell.config["serve"]["max_len"]
        sup = gen.support(cell.traffic)
        assert sup["max_prompt"] + sup["max_new"] - 1 <= max_len
        for prompt, out, _ in _trace(cell.traffic, SEED,
                                     slots=cell.config["serve"]["slots"]):
            assert len(prompt) + out - 1 <= max_len


def test_open_loop_rate_and_window():
    mix = dict(MIXES["open"], rate=2.5)
    reqs = gen.open_loop(mix, SEED, 40.0, 1000)
    assert len(reqs) == 100
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] <= 40.0 + 1e-9


def test_grid_round():
    grid = [64, 128, 256]
    assert [gen.grid_round(x, grid) for x in (1, 64, 65, 200, 999)] == [
        64, 64, 128, 256, 256]


def test_token_ids_in_vocab():
    for r in gen.wave(MIXES["chat"], SEED, 0, 4, 77):
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 77
