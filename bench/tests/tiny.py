"""A throw-away benchmark root for CPU tests: a copy of ``bench/`` and
``BENCHMARK.json`` plus a tiny configuration and traffic mix, added as new
files the way a later change would add them."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_MODEL = {"family": "dense", "num_layers": 2, "d_model": 64,
              "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
              "d_ff": 128, "vocab_size": 256, "rope_theta": 10000.0,
              "norm_eps": 1e-06, "act": "gelu", "use_bias": False,
              "tie_embeddings": False, "dtype": "bfloat16",
              "param_dtype": "bfloat16"}

TINY_MIXES = {
    "tiny-offline": {
        "kind": "offline", "wave_per_slot": 2,
        "prompt": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                   "grid": [32, 64]},
        "output": {"dist": "loguniform", "min": 4, "max": 24},
        "check": {"tokens": 48, "max_requests": 4}},
    "tiny-open": {
        "kind": "open_loop", "rate": 12.0,
        "prompt": {"dist": "lognormal", "median": 48, "sigma": 0.5,
                   "grid": [32, 64]},
        "output": {"dist": "uniform", "min": 2, "max": 6},
        "check": {"tokens": 24, "max_requests": 6}},
    # One prompt length and one output length: the slots of a wave decode
    # in lockstep, so every decode bucket holds two requests.
    "tiny-lockstep": {
        "kind": "offline", "wave_per_slot": 2,
        "prompt": {"dist": "lognormal", "median": 32, "sigma": 0.1,
                   "grid": [32]},
        "output": {"dist": "uniform", "min": 6, "max": 6},
        "check": {"tokens": 24, "max_requests": 4}},
}


def make_root(tmp: Path, gap_limit: float = 0.25) -> Path:
    """``tmp`` laid out as a checkout holding the benchmark, with the
    cells ``tiny.offline``, ``tiny.open`` and ``tiny.lockstep`` added."""
    root = Path(tmp)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    config = {"name": "tiny", "source": "test", "reference": "dense_decoder",
              "model": TINY_MODEL, "serve": {"slots": 2, "max_len": 128},
              "reduced": [], "check": {"served_logit_gap": gap_limit}}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    for name, mix in TINY_MIXES.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    for cell, mix in (("tiny.offline", "tiny-offline"),
                      ("tiny.open", "tiny-open"),
                      ("tiny.lockstep", "tiny-lockstep")):
        bench["workloads"].append({"name": cell, "config": "tiny",
                                   "traffic": mix, "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m.get("moves", m["name"]) == "tokens_per_s":
            m["workloads"].extend(["tiny.offline", "tiny.lockstep"])
    # The open loop's tails, added the way a later cell would add them.
    bench["end_to_end"][:0] = [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tiny.open"]}
        for name in ("ttft_p90_ms", "tpot_p90_ms")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
