"""Share of the HBM bandwidth that pure-decode steps would need for the
bytes decoding must move (``bench.counts.decode_step_bytes``: weights once,
embedding rows, each slot's valid K/V, the new K/V), over those steps'
host time.  Counted from the configuration, so no implementation can read
above 100%."""
from bench import counts


def read(run):
    if run.peaks is None or run.decode_wall_s <= 0:
        return None
    moved = sum(counts.decode_step_bytes(run.sizes, s["kv_lens"])
                for s in run.steps if s["pure_decode"])
    return 100.0 * moved / (run.decode_wall_s
                            * run.peaks["hbm_bytes_per_s"])
