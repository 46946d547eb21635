#!/usr/bin/env python3
"""Attribute a traced window to the program's own names: device time to
the programs that ran, device idle time to the host spans the engine opened
(``repro.obs.spans``) around what it was doing.

    python3 bench/trace_spans.py <trace directory or .xplane.pb>

prints the reduction as one JSON object.  ``reduce_spans`` gives:

* ``program_s``: device seconds per program, from the ``XLA Modules`` events
  of each device plane clipped to the ``window`` span (averaged over the
  devices traced), each name with its fingerprint stripped
  (``jit_kv_gather(1234)`` and ``jit_kv_gather_1234_`` are ``jit_kv_gather``);
* ``idle_s``: the first device's idle time in the window (as
  ``bench.trace_reduce`` computes busy time);
* ``idle_in``: that idle time split over the innermost host span covering
  each part of it (the one that started last; ``window`` where none does),
  the idle time inside each span name's spans, nested spans included, and
  each span's count in the window:
  ``{name: {"idle_s", "idle_within_s", "count"}}``;
* ``idle_by_span``: ``[[name, idle_s], ...]``, largest first;
* ``pure_decode``: the idle time inside ``engine.step`` spans that hold an
  ``engine.decode`` and no ``engine.admit``, and the count of those steps.

Device time is attributed by program name and never by span: a span does
not wait for the device, which may still run what an earlier span
dispatched.  Idle time is attributed by span: while the device idles, the
host is what it waits for.
"""
from __future__ import annotations

import bisect
import collections
import heapq
import json
import os
import re
import sys
from typing import Dict, List, Sequence, Tuple

if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))]

from bench import trace_reduce as tr  # noqa: E402

# The spans ``serve.Engine`` and its K/V pool open, and the harness's own.
ENGINE_SPANS = ("engine.step", "engine.admit", "engine.prefill",
                "engine.sync", "kv.write", "engine.plan_decode",
                "engine.decode", "kv.gather", "model.decode_step",
                "kv.scatter", "engine.retire")
HARNESS_SPANS = ("engine.run", "client.wait_arrival")

Span = Tuple[float, float, str]
_FINGERPRINT = re.compile(r"(\(\d+\)|_\d+_)$")


def program(name: str) -> str:
    """A device program's name without its fingerprint."""
    return _FINGERPRINT.sub("", name)


def innermost(spans: Sequence[Span], win: tr.Interval) -> List[Span]:
    """The window cut into pieces, each named by the innermost span that
    covers it: of the spans covering a piece, the one that started last
    (the shorter on a tie); ``window`` where none covers it."""
    cuts = sorted({win[0], win[1]} | {t for a, b, _ in spans for t in (a, b)
                                      if win[0] < t < win[1]})
    order = sorted(spans)
    heap: List[Tuple[float, float, str]] = []
    k, out = 0, []
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and order[k][0] <= a:
            s = order[k]
            heapq.heappush(heap, (-s[0], s[1], s[2]))
            k += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        out.append((a, b, heap[0][2] if heap else tr.WINDOW))
    return out


def split(holes: Sequence[tr.Interval], pieces: Sequence[Span]
          ) -> Dict[str, float]:
    """The length of ``holes`` (sorted, disjoint) that falls in each named
    piece (sorted, disjoint), summed by name."""
    out: Dict[str, float] = collections.Counter()
    j = 0
    for a, b in holes:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            if hi > lo:
                out[pieces[k][2]] += hi - lo
            k += 1
    return out


def _holds(starts: Sequence[float], a: float, b: float) -> bool:
    """Whether any of the sorted ``starts`` lies in ``[a, b]``."""
    k = bisect.bisect_left(starts, a)
    return k < len(starts) and starts[k] <= b


def _busy(plane, win: tr.Interval) -> List[tr.Interval]:
    events = (tr._events(plane, tr.OPS_LINE)
              or tr._events(plane, tr.MODULES_LINE))
    return tr.union(c for c in (tr._clip((a, b), win)
                                for a, b, _ in events) if c)


def _holes(busy: List[tr.Interval], win: tr.Interval) -> List[tr.Interval]:
    holes, edge = [], win[0]
    for a, b in busy + [(win[1], win[1])]:
        if a > edge:
            holes.append((edge, a))
        edge = max(edge, b)
    return holes


def reduce_spans(path: str,
                 span_names: Sequence[str] = ENGINE_SPANS + HARNESS_SPANS
                 ) -> Dict:
    """The reduction described above, of the trace at ``path``.  Raises
    where the trace holds no window span or no device plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = tr._host_spans(pd, span_names)
    windows = [(a, b) for a, b, n in spans if n == tr.WINDOW]
    if not windows:
        raise ValueError(f"{path}: no host span named {tr.WINDOW!r}")
    win = (min(a for a, _ in windows), max(b for _, b in windows))
    planes = tr._device_planes(pd)
    if not planes:
        raise ValueError(f"{path}: no device plane")

    prog: Dict[str, float] = collections.Counter()
    for plane in planes:
        for a, b, name in tr._events(plane, tr.MODULES_LINE):
            c = tr._clip((a, b), win)
            if c is not None:
                prog[program(name)] += (c[1] - c[0]) / len(planes)
    holes = _holes(_busy(planes[0], win), win)

    inner = [(max(a, win[0]), min(b, win[1]), n) for a, b, n in spans
             if n != tr.WINDOW and b > win[0] and a < win[1]]
    idle = split(holes, innermost(inner, win))
    counts = collections.Counter(n for _, _, n in inner)

    admits = sorted(a for a, _, n in inner if n == "engine.admit")
    decodes = sorted(a for a, _, n in inner if n == "engine.decode")

    pure = sorted((a, b, "pure") for a, b, n in inner if n == "engine.step"
                  and _holds(decodes, a, b) and not _holds(admits, a, b))
    names = sorted(set(counts) | set(idle))
    within = {n: split(holes, sorted(s for s in inner if s[2] == n))
              .get(n, 0.0) for n in counts}
    return {
        "window_s": (win[1] - win[0]) * 1e-9,
        "idle_s": sum(b - a for a, b in holes) * 1e-9,
        "program_s": {n: t * 1e-9 for n, t in
                      sorted(prog.items(), key=lambda kv: -kv[1])},
        "idle_in": {n: {"idle_s": idle.get(n, 0.0) * 1e-9,
                        "idle_within_s": within.get(n, idle.get(n, 0.0))
                        * 1e-9,
                        "count": counts.get(n, 0)} for n in names},
        "idle_by_span": [[n, t * 1e-9] for n, t in
                         sorted(idle.items(), key=lambda kv: -kv[1])],
        "pure_decode": {"idle_s": split(holes, pure).get("pure", 0.0) * 1e-9,
                        "steps": len(pure)},
    }


if __name__ == "__main__":
    arg = sys.argv[1]
    path = arg if arg.endswith(".xplane.pb") else tr.find_trace(arg)
    print(json.dumps(reduce_spans(path)))
