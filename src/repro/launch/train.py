"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b \
        --shape train_4k --steps 100 [--smoke] [--mode tile_stream] \
        [--checkpoint-dir ckpts/run1] [--microbatches 4]

``--smoke`` uses the arch's reduced config and tiny shapes.  The mesh is
built from the devices present (``launch.mesh.make_local_mesh``): one
device runs the 1x1 mesh, a four-chip host shards over all four.
"""
from __future__ import annotations

import argparse
import dataclasses

from repro.configs import registry
from repro.core.compile_cache import use_persistent_cache
from repro.core.types import ExecutionMode, SHAPES, ShapeConfig
from repro.data.pipeline import SyntheticLM, TextCorpus
from repro.launch.mesh import make_local_mesh
from repro.train import loop as L
from repro.train import optimizer as OPT


def main() -> None:
    use_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(registry.ARCHS), required=True)
    ap.add_argument("--shape", choices=list(SHAPES), default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny shapes on the host mesh")
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--mode", choices=[m.value for m in ExecutionMode],
                    default=None)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--corpus", default=None,
                    help="path to local text corpus (default: synthetic)")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    shape = SHAPES[args.shape]
    if args.smoke:
        shape = ShapeConfig("smoke", args.seq_len or 128,
                            args.global_batch or 8, "train")
    elif args.global_batch or args.seq_len:
        shape = dataclasses.replace(
            shape, global_batch=args.global_batch or shape.global_batch,
            seq_len=args.seq_len or shape.seq_len)

    mesh = make_local_mesh()

    source = (TextCorpus(cfg, shape, args.corpus) if args.corpus
              else SyntheticLM(cfg, shape))
    mode = ExecutionMode(args.mode) if args.mode else None
    tcfg = L.TrainConfig(
        steps=args.steps, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every, mode=mode,
        use_pallas=args.use_pallas, microbatches=args.microbatches,
        opt=OPT.OptimizerConfig(learning_rate=args.lr,
                                decay_steps=args.steps))

    def on_log(m):
        print(f"step {m['step']:6d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:.3f}  lr {m['lr']:.2e}  "
              f"{m['steps_per_s']:.2f} it/s", flush=True)

    L.train(cfg, shape, source, mesh, tcfg, hooks={"on_log": on_log})


if __name__ == "__main__":
    main()
