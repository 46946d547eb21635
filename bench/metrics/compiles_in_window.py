"""Programs compiled or loaded from the persistent cache inside the
window (``core.compile_cache.CompileCounter``); 0 when warm-up covered
every shape and nothing re-traces."""


def read(run):
    return run.compiles
