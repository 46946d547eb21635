#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload minitron-4b.chat --seed 7 \
        --seconds 45 --trace 0

The cells, their configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the root of the checkout; ``bench/harness.py`` says
how each is found and driven.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
with ``--trace 1`` a ``breakdown`` of the trace, and last the ``checks``
behind ``correct``, each number beside its limit.  Without a TPU, or with
fewer chips than the cell asks for, it exits nonzero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness
    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
