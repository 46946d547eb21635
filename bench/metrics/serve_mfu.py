"""Model FLOPs of the work completed in the window (each request's prefill
and decodes, ``bench.counts.request_flops``) over the window times the
chip's peak bf16 FLOP/s, in percent: the whole step's share of the peak."""
from bench import counts


def read(run):
    if run.peaks is None or run.window_s <= 0:
        return None
    flops = sum(counts.request_flops(run.sizes, r["prompt_len"],
                                     len(r["out"]))
                for r in run.requests if r["out"])
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops"])
