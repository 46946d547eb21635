"""Output tokens completed per second of the window, per chip (one chip)."""


def read(run):
    tokens = sum(len(r["out"]) for r in run.requests)
    return tokens / run.window_s if run.window_s > 0 else None
