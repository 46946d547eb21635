#!/usr/bin/env python3
"""Chip smoke test: minitron-4b at its published widths on a TPU.

    python chip_smoke.py             # one chip: serve, logit check, kernels
    python chip_smoke.py --chips 4   # four chips: sharded training steps only

One process, no subprocesses.  The persistent compile cache is placed
before JAX compiles anything (``repro.core.compile_cache``): where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``.

Phases (one chip):

* serve — ``serve.Engine`` (4 slots) answers 8 seeded requests through
  ``Engine.run``; every request must get all its tokens.
* correctness — ``prefill`` and a few ``decode_step`` calls against
  ``transformer.forward`` over the same tokens, logits compared in
  float32 (bound: ``LOGIT_TOL`` of the largest reference logit).
* kernels — ``flash_attention``, ``tile_gemm`` and B=4
  ``decode_attention`` through ``kernels.ops`` with ``use_pallas=True``,
  each against its ``kernels/ref.py`` oracle; each compiled program must
  hold a ``tpu_custom_call`` (native Pallas, not interpret mode).

With ``--chips 4``: a few ``train.loop.train`` steps over the four local
chips with parameters and optimizer state sharded by
``SH.param_shardings``; the first step's loss must match the one-chip
forward loss of the same seeded parameters and batch within ``LOSS_TOL``.

Weights and requests are random from ``SEED``.  Earlier lines report
counts, memory and cold-run wall times (compiles included); no rate.
The last line is ``{"ok": true, "device": {...}}``.  Without a TPU, or
if any check fails, the script exits nonzero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core.compile_cache import CompileCounter, use_persistent_cache  # noqa: E402

ARCH = "minitron-4b"
SEED = 0
SLOTS = 4
MAX_LEN = 2048
PROMPT_LENS = (64, 256, 512, 1024)   # few distinct lengths: bounded prefills
NEW_TOKENS = (16, 32)
N_REQUESTS = 8
# bf16 weights and activations through 32 layers: serve and reference
# differ only in reduction order and attention blocking.
LOGIT_TOL = 5e-2           # max |serve - ref| <= LOGIT_TOL * max |ref|
KERNEL_TOL = 2e-2          # max |kernel - oracle| <= KERNEL_TOL * max |oracle|
LOSS_TOL = 1e-2            # |sharded - one chip| <= LOSS_TOL * one-chip loss
TRAIN_STEPS = 3
TRAIN_SEQ = 1024
TRAIN_BATCH = 2              # 4 fits in 14.6 of 16 GiB per chip; 2 leaves room


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def _log(msg: str) -> None:
    print(msg, flush=True)


def _tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def make_requests(vocab: int, *, seed: int, n: int = N_REQUESTS,
                  prompt_lens=PROMPT_LENS, new_tokens=NEW_TOKENS):
    """``n`` seeded requests: prompt lengths drawn from ``prompt_lens``
    (each at least once when ``n`` allows), ``new_tokens`` = (lo, hi)
    inclusive, every other request arriving a few steps late."""
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    lens = list(prompt_lens) + list(rng.choice(prompt_lens, n))
    reqs = []
    for i in range(n):
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, vocab, size=(int(lens[i]),),
                                dtype=np.int32),
            max_new_tokens=int(rng.integers(new_tokens[0],
                                            new_tokens[1] + 1)),
            arrival_step=0 if i % 2 == 0 else int(rng.integers(1, 6))))
    return reqs


def serve_phase(cfg, *, seed: int, slots: int = SLOTS,
                max_len: int = MAX_LEN, **request_kw):
    """Build the engine as ``launch/serve.py`` does and drain seeded
    requests through ``Engine.run``.  Returns (report, params)."""
    from repro.launch.serve import build_engine
    eng = build_engine(cfg, seed=seed, slots=slots, max_len=max_len)
    reqs = make_requests(cfg.vocab_size, seed=seed, **request_kw)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    _require(len(done) == len(reqs),
             f"served {len(done)} of {len(reqs)} requests")
    for r in done:
        _require(len(r.out_tokens) == r.max_new_tokens,
                 f"request {r.rid}: {len(r.out_tokens)} of "
                 f"{r.max_new_tokens} tokens")
        _require(all(0 <= t < cfg.vocab_size for t in r.out_tokens),
                 f"request {r.rid}: token outside the vocabulary")
    st = eng.stats()
    report = {"requests": len(done),
              "tokens": sum(len(r.out_tokens) for r in done),
              "prompt_lens": sorted({len(r.prompt) for r in reqs}),
              "steps": st["steps"], "decode_batches": st["decode_batches"]}
    params = eng.params
    del eng                   # the engine's K/V pool goes with it
    gc.collect()
    return report, params


def correctness_phase(cfg, params, *, seed: int, prompt_len: int = 64,
                      decode_steps: int = 4, max_len: int = MAX_LEN):
    """Logits of ``prefill`` + ``decode_steps`` greedy ``decode_step``
    calls vs ``forward`` over the same tokens, in float32.  Returns
    {"max_abs_diff", "ref_scale", "rel"}; fails past ``LOGIT_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import registry
    from repro.plan import plan_model
    mod = registry.model_module(cfg)
    V = cfg.vocab_size
    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(0, V, size=(1, prompt_len),
                                    dtype=np.int32))
    logits, cache = mod.prefill(params, cfg, {"tokens": toks},
                                max_len=max_len,
                                plan=plan_model(cfg, seq_len=prompt_len))
    rows = [logits[0].astype(jnp.float32)]
    decode = jax.jit(lambda p, c, t: mod.decode_step(p, cfg, c, t))
    tok = jnp.argmax(logits[:, -1, :V], axis=-1)[:, None].astype(jnp.int32)
    fed = [tok]
    for _ in range(decode_steps):
        step_logits, cache = decode(params, cache, tok)
        rows.append(step_logits[0].astype(jnp.float32))
        tok = jnp.argmax(step_logits[:, -1, :V], axis=-1)[:, None].astype(
            jnp.int32)
        fed.append(tok)
    served = jnp.concatenate(rows, axis=0)[:, :V]
    all_toks = jnp.concatenate([toks] + fed[:-1], axis=1)
    ref = jax.jit(lambda p, t: mod.forward(p, cfg, {"tokens": t}))(
        params, all_toks)[0, :, :V].astype(jnp.float32)
    _require(bool(jnp.all(jnp.isfinite(served)))
             and bool(jnp.all(jnp.isfinite(ref))), "non-finite logits")
    diff = float(jnp.max(jnp.abs(served - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    out = {"max_abs_diff": diff, "ref_scale": scale, "rel": diff / scale,
           "positions": int(served.shape[0])}
    _require(diff <= LOGIT_TOL * scale,
             f"logits differ by {diff} > {LOGIT_TOL} x {scale}")
    return out


def kernel_phase(cfg, *, seed: int):
    """Each main-path Pallas kernel at ``cfg``'s widths, compiled
    natively, against its oracle.  Returns per kernel its largest
    difference and the oracle's largest magnitude."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.plan import plan_decode_step
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda i, shape: jax.random.normal(ks[i], shape, bf)   # noqa: E731
    S = 1024
    lens = (MAX_LEN, 1000, 333, 65)             # ragged B=4 bucket
    lp = plan_decode_step(cfg, lens).layers[0]
    cases = {
        "flash_attention": (
            lambda q, k, v: ops.multi_head_attention(
                q, k, v, causal=True, use_pallas=True),
            lambda q, k, v: ref.ref_attention(q, k, v, causal=True),
            (n(0, (1, hq, S, hd)), n(1, (1, hkv, S, hd)),
             n(2, (1, hkv, S, hd)))),
        "tile_gemm": (
            lambda x, w: ops.projection(x, w, use_pallas=True),
            ref.ref_tile_gemm,
            (n(3, (S, cfg.d_model)), n(4, (cfg.d_model, cfg.d_ff)))),
        "decode_attention": (
            lambda q, k, v, c: ops.batched_decode_attention_by_plan(
                lp, q, k, v, c, use_pallas=True),
            lambda q, k, v, c: ref.ref_decode_attention(q, k, v, c),
            (n(5, (len(lens), hq, 1, hd)),
             n(6, (len(lens), hkv, MAX_LEN, hd)),
             n(7, (len(lens), hkv, MAX_LEN, hd)),
             jnp.asarray(lens, jnp.int32))),
    }
    out = {}
    for name, (fn, oracle, args) in cases.items():
        compiled = jax.jit(fn).lower(*args).compile()
        _require("tpu_custom_call" in compiled.as_text(),
                 f"{name}: no tpu_custom_call in the compiled program")
        got = compiled(*args).astype(jnp.float32)
        want = jax.jit(oracle)(*args).astype(jnp.float32)
        _require(bool(jnp.all(jnp.isfinite(got))), f"{name}: non-finite")
        diff = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want)))
        _require(diff <= KERNEL_TOL * scale,
                 f"{name}: differs from its oracle by {diff} > "
                 f"{KERNEL_TOL} x {scale}")
        out[name] = {"max_abs_diff": diff, "oracle_scale": scale}
    return out


def train_phase(cfg, *, seed: int, steps: int = TRAIN_STEPS,
                seq_len: int = TRAIN_SEQ, global_batch: int = TRAIN_BATCH):
    """One-chip forward loss, then ``steps`` sharded training steps over
    every local device from the same seed.  Returns the losses."""
    import jax
    from repro.configs import registry
    from repro.core.types import ShapeConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_local_mesh
    from repro.train import loop as TL
    mod = registry.model_module(cfg)
    shape = ShapeConfig("chip-smoke", seq_len, global_batch, "train")
    source = SyntheticLM(cfg, shape, seed=seed)
    with jax.default_device(jax.devices()[0]):
        params = jax.jit(lambda k: mod.init(k, cfg))(jax.random.PRNGKey(seed))
        batch = jax.tree.map(jax.numpy.asarray, source.batch(0))
        ref_loss = float(jax.jit(
            lambda p, b: mod.loss_fn(p, cfg, b, remat=True))(params, batch))
    del params, batch
    gc.collect()
    mesh = make_local_mesh()
    out = TL.train(cfg, shape, source, mesh,
                   TL.TrainConfig(steps=steps, log_every=1, seed=seed))
    losses = [m["loss"] for m in out["metrics"]]
    del out
    gc.collect()
    import math
    _require(all(math.isfinite(x) for x in losses + [ref_loss]),
             f"non-finite loss: one chip {ref_loss}, sharded {losses}")
    _require(abs(losses[0] - ref_loss) <= LOSS_TOL * abs(ref_loss),
             f"first sharded loss {losses[0]} vs one-chip {ref_loss}")
    return {"one_chip_loss": ref_loss, "sharded_losses": losses,
            "mesh": dict(mesh.shape)}


def _phase(name: str, fn, device):
    """Run one phase; log its result, compiles, peak memory and cold-run
    wall time."""
    t0 = time.perf_counter()
    with CompileCounter() as cc:
        result = fn()
    wall = time.perf_counter() - t0
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    _log(f"[{name}] {json.dumps(result, default=str)}")
    _log(f"[{name}] compiled {cc.compiled}, persistent-cache hits "
         f"{cc.cache_hits}, peak_bytes_in_use {peak}, "
         f"cold-run wall time {wall:.1f} s")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded training phase")
    args = ap.parse_args(argv)
    cache_dir = use_persistent_cache()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) present", file=sys.stderr)
        return 1
    dev = devices[0]
    _log(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
         f"compile cache {cache_dir}")

    from repro.configs import registry
    cfg = registry.get_config(ARCH)
    with CompileCounter() as total:
        if args.chips == 4:
            _phase("train", lambda: train_phase(cfg, seed=SEED), dev)
        else:
            held = {}

            def serve():
                report, held["params"] = serve_phase(cfg, seed=SEED)
                report["model"] = cfg.name
                report["param_bytes"] = _tree_bytes(held["params"])
                return report

            _phase("serve", serve, dev)
            _phase("correctness", lambda: correctness_phase(
                cfg, held["params"], seed=SEED), dev)
            held.clear()
            gc.collect()
            _phase("kernels", lambda: kernel_phase(cfg, seed=SEED), dev)
    _log(f"total: compiled {total.compiled}, persistent-cache hits "
         f"{total.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
