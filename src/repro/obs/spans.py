"""Named host spans on the profiler's own clock, and the one counted
device-to-host read of the serving loop.

``span(name, **meta)`` is ``jax.profiler.TraceAnnotation``: while a
profiler trace runs it writes a host event that shares the device planes'
clock, so a reduction can name every stretch of device idle time by the
host work that held it; while none runs it costs a Python context manager
(about 2 us on a CPU core) and formats nothing.  Names are fixed strings
and ``meta`` takes plain values, never preformatted text.  A span never
waits for the device: time the device runs ahead of the host is not
charged to the span that dispatched it.

``host_read`` is the loop's only way to bring a device value to the host:
it opens ``engine.sync`` around the read and counts it.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.obs.metrics import Counter

span = jax.profiler.TraceAnnotation
step_span = jax.profiler.StepTraceAnnotation


def host_read(x, syncs: Optional[Counter] = None) -> np.ndarray:
    """``np.asarray(x)`` inside an ``engine.sync`` span, counted on
    ``syncs`` (the engine's ``host_syncs``) where one is given."""
    with span("engine.sync"):
        out = np.asarray(x)
    if syncs is not None:
        syncs.inc()
    return out
