"""The one seeded traffic generator: every mix under ``bench/traffic/`` is a
JSON file of parameters that this module reads.

A mix fixes the requests (each prompt length paired with its output
length), their order and the arrival gaps; the seed draws only the token
ids.  So every seed asks the engine for the same work in the same
schedule, and runs of different seeds spread no wider than runs of one
seed (the order of lengths decides which requests share decode steps, so
a seeded order would change the work).  Sizes come from stratified
quantiles of the mix's distributions:

* prompt lengths: ``lognormal`` (``median``, ``sigma``), rounded *up* to
  the mix's ``grid`` of lengths and capped at its largest entry;
* output lengths: ``loguniform`` or ``uniform`` over ``[min, max]``;
* ``offline`` mixes: waves of ``wave_per_slot x slots`` requests, all due
  when the wave is submitted.  Every wave asks for the same lengths in the
  same order (only the ids differ), so each wave does the same work, a
  window's rate does not hang on how many waves it holds, and serving one
  wave in set-up warms every shape the window reaches;
* ``open_loop`` mixes: ``round(rate x seconds)`` arrivals whose gaps are
  stratified exponential quantiles (a Poisson process at ``rate`` with a
  fixed count), scaled so the last arrival falls at ``seconds``.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np

@dataclasses.dataclass(frozen=True)
class Req:
    """One generated request: its prompt ids, output budget and, for an
    open loop, when it is due (seconds after the window opens)."""

    prompt: np.ndarray
    max_new_tokens: int
    due_s: float = 0.0


def grid_round(x: float, grid: Sequence[int]) -> int:
    """``x`` rounded up to the next grid length, capped at the largest."""
    for g in sorted(grid):
        if x <= g:
            return int(g)
    return int(max(grid))


def _quantiles(n: int, offset: float) -> np.ndarray:
    return (np.arange(n) + offset) / n


def prompt_lengths(spec: Dict, n: int, offset: float = 0.5) -> List[int]:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown prompt distribution {spec['dist']!r}")
    nd = NormalDist()
    out = []
    for p in _quantiles(n, offset):
        z = nd.inv_cdf(float(p))
        out.append(grid_round(spec["median"] * math.exp(spec["sigma"] * z),
                              spec["grid"]))
    return out


def output_lengths(spec: Dict, n: int, offset: float = 0.5) -> List[int]:
    lo, hi = spec["min"], spec["max"]
    ps = _quantiles(n, offset)
    if spec["dist"] == "loguniform":
        vals = lo * (hi / lo) ** ps
    elif spec["dist"] == "uniform":
        vals = lo + (hi - lo + 1) * ps - 0.5
    else:
        raise ValueError(f"unknown output distribution {spec['dist']!r}")
    return [int(min(hi, max(lo, round(float(v))))) for v in vals]


def support(mix: Dict) -> Dict[str, int]:
    """Every size a mix can produce, whatever the seed: the grid lengths
    it uses and the longest output.  Warm-up covers exactly these."""
    return {"grid": sorted(int(g) for g in mix["prompt"]["grid"]),
            "min_prompt": min(mix["prompt"]["grid"]),
            "max_prompt": max(mix["prompt"]["grid"]),
            "max_new": int(mix["output"]["max"])}


def check_fits(mix: Dict, max_len: int) -> None:
    s = support(mix)
    if s["max_prompt"] + s["max_new"] - 1 > max_len:
        raise ValueError(f"mix needs {s['max_prompt']} + {s['max_new']} - 1 "
                         f"positions; the engine holds {max_len}")


def _ids(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=(n,), dtype=np.int32)


def _pairs(mix: Dict, n: int, p_off: float, o_off: float,
           key: List[int]) -> List[tuple]:
    """``n`` (prompt length, output length) pairs, joined and ordered by
    permutations that depend on ``key`` and never on the seed."""
    prompts = prompt_lengths(mix["prompt"], n, p_off)
    outs = output_lengths(mix["output"], n, o_off)
    rng = np.random.default_rng([7, *key])
    perm = rng.permutation(n)
    pairs = [(p, outs[i]) for p, i in zip(prompts, perm)]
    return [pairs[i] for i in rng.permutation(n)]


def wave(mix: Dict, seed: int, index: int, slots: int, vocab: int,
         stream: int = 1) -> List[Req]:
    """Wave ``index`` of an offline mix: the same requests in the same
    order for every seed and every index, with token ids drawn from the
    seed, the index and ``stream`` (set-up draws from a stream of its
    own)."""
    n = int(mix["wave_per_slot"]) * slots
    pairs = _pairs(mix, n, 0.5, 0.5, [0])
    rng = np.random.default_rng([int(seed), stream, index])
    return [Req(_ids(rng, p, vocab), o) for p, o in pairs]


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """All arrivals of an open-loop mix over ``seconds``, in due order."""
    n = max(1, int(round(float(mix["rate"]) * seconds)))
    pairs = _pairs(mix, n, 0.5, 0.5, [])
    gaps = -np.log1p(-_quantiles(n, 0.5))       # Exp(1) quantiles
    np.random.default_rng([7, 1]).shuffle(gaps)
    rng = np.random.default_rng([int(seed), 1])
    due = np.cumsum(gaps) * (seconds / float(np.sum(gaps)))
    return [Req(_ids(rng, p, vocab), o, float(t))
            for (p, o), t in zip(pairs, due)]
