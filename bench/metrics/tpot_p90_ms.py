"""p90 over the window's requests with two tokens or more of
(finish - first token) / (tokens - 1)."""
from bench.stats import percentile


def read(run):
    return percentile([(r["finish"] - r["first_token"]) * 1e3
                       / (len(r["out"]) - 1)
                       for r in run.requests
                       if len(r["out"]) >= 2 and r["finish"] is not None],
                      0.9)
