"""Serving launcher: batched generation with the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b --smoke \
        --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import registry
from repro.core.compile_cache import use_persistent_cache
from repro.core.types import ModelConfig
from repro.serve.engine import Engine, Request


def build_engine(cfg: ModelConfig, *, seed: int = 0, slots: int = 4,
                 max_len: int = 256) -> Engine:
    """Random parameters from ``seed`` and an engine serving them.

    Init is jitted with ``cfg`` static, so parameters are born in their
    own dtype on the device (an eager init would draw every float32
    temporary, 3.1 GB for a 256k x 3072 embedding)."""
    mod = registry.model_module(cfg)
    params = jax.jit(lambda k: mod.init(k, cfg))(jax.random.PRNGKey(seed))
    return Engine(cfg, params, slots=slots, max_len=max_len)


def main() -> None:
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(registry.ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    args = ap.parse_args()

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    eng = build_engine(cfg, slots=args.slots, max_len=args.max_len)

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.integers(4, args.max_len // 4))
        eng.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                       size=(plen,)).astype(np.int32),
            max_new_tokens=args.max_new))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    total = sum(len(r.out_tokens) for r in done)
    print(f"{len(done)} requests, {total} new tokens, {dt:.2f}s "
          f"({total / dt:.1f} tok/s) on {jax.devices()[0].platform}")


if __name__ == "__main__":
    main()
