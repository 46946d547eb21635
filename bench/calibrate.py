#!/usr/bin/env python3
"""Readings that set a cell's limits and rate; run on the chip, by hand.

    # served_logit_gap of the program and of the float8 control, per seed
    python3 bench/calibrate.py gaps --workload minitron-4b.chat \
        --seeds 1,2,3 --seconds 20 [--control]
    # (with --control, correct must come out false on every seed)

    # open-loop knee: an open-loop cell's mix at each rate, one window each
    python3 bench/calibrate.py sweep --workload <open-loop cell> \
        --rates 1,1.5,2,2.5,3 --seconds 30 --seed 1

One process drives every seed or rate, so set-up is paid once per seed and
the compile cache is shared.  Each reading is one JSON line on standard
output.  The benchmark's own runs never run the control.
"""
import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def _emit(obj):
    print(json.dumps(obj), flush=True)


def gaps(cell, seeds, seconds, control):
    """One full run of the cell per seed, each decided by the harness's own
    comparison; with ``control`` the float8 reference takes the program's
    place there, and the program's reading of the same window is kept."""
    from bench import harness
    for seed in seeds:
        res = harness.run_cell(cell, seed=seed, seconds=seconds,
                               trace=False, t_start=time.perf_counter(),
                               control=control)
        _emit({"seed": seed, "control": control, **res})
        gc.collect()


def sweep(cell, rates, seconds, seed):
    from bench import harness
    from bench.stats import percentile
    params, client = harness.build(cell, seed)
    eng, req_cls = client.eng, client.request_cls
    for rate in rates:
        cell.traffic = dict(cell.traffic, rate=rate)
        c = harness.Client(eng, req_cls)
        window = harness.drive(c, cell, seed, seconds)
        rs = c.requests
        n = len(rs)
        wait = [(r["admit"] - r["due"]) * 1e3 for r in rs]
        third = max(1, n // 3)
        _emit({"rate": rate, "requests": n, "window_s": window,
               "overshoot_s": window - seconds,
               "ttft_p50_ms": percentile([(r["first_token"] - r["due"]) * 1e3
                                          for r in rs], 0.5),
               "ttft_p90_ms": percentile([(r["first_token"] - r["due"]) * 1e3
                                          for r in rs], 0.9),
               "wait_first_third_ms": sum(wait[:third]) / third,
               "wait_last_third_ms": sum(wait[-third:]) / third})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("gaps", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    from repro.core.compile_cache import use_persistent_cache
    use_persistent_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    if args.mode == "gaps":
        gaps(cell, [int(s) for s in args.seeds.split(",")], args.seconds,
             args.control)
    else:
        sweep(cell, [float(r) for r in args.rates.split(",")],
              args.seconds, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
