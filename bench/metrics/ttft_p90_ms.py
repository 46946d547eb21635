"""p90 over all requests due in the window of the time from when each was
due to when its first token reached the host; queueing included."""
from bench.stats import percentile


def read(run):
    return percentile([(r["first_token"] - r["due"]) * 1e3
                       for r in run.requests
                       if r["first_token"] is not None], 0.9)
