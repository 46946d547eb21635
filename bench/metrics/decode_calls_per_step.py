"""Batched ``decode_step`` calls per engine step that decoded: 1 is one
call for all active slots, about ``slots`` is one call per slot."""


def read(run):
    decoding = sum(1 for s in run.steps if s["kv_lens"])
    return run.decode_batches / decoding if decoding else None
