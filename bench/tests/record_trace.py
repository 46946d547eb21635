#!/usr/bin/env python3
"""Record the small TPU trace that ``test_bench_trace.py`` reduces: the
tiny offline cell of ``bench/tests/tiny.py``, traced for a short window.

    python3 bench/tests/record_trace.py bench/testdata

Needs a TPU.  Writes ``tiny_offline.xplane.pb`` into the directory given
and prints the reduction of it as one JSON line.
"""
import json
import os
import shutil
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def main(out_dir: str) -> int:
    import jax
    from bench import harness, trace_reduce
    from bench.tests import tiny
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    try:
        root = tiny.make_root(os.path.join(tmp, "root"))
        cell = harness.load_cell("tiny.offline", root)
        keep = os.path.join(tmp, "trace")
        res = harness.run_cell(cell, seed=1, seconds=0.3, trace=True,
                               t_start=time.perf_counter(), keep_trace=keep)
        os.makedirs(out_dir, exist_ok=True)
        dest = os.path.join(out_dir, "tiny_offline.xplane.pb")
        shutil.copyfile(trace_reduce.find_trace(keep), dest)
        red = trace_reduce.reduce_trace(dest, harness.SPANS)
        print(json.dumps({"correct": res["correct"], "bytes":
                          os.path.getsize(dest), **red}))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
