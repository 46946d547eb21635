"""Mean host time of an engine step that only decoded (no admission):
``decode_wall_s()`` over the number of such steps."""


def read(run):
    n = sum(1 for s in run.steps if s["pure_decode"])
    return run.decode_wall_s / n * 1e3 if n else None
