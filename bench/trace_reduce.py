"""Reduce a profiler trace (``.xplane.pb``) of one measured window to the
device's busy time, its heaviest operations and its idle gaps.

* The window is the host span named ``window`` (a ``TraceAnnotation`` the
  harness opens around the timed loop); everything is clipped to it.
* Busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane; where a plane
  has none, its ``XLA Modules`` line), averaged over the devices traced.
* Each operation is named ``<program>/<op>``: the program is the
  ``XLA Modules`` event that encloses it on the same device.
* Each idle gap of the first device is named by the innermost host span of
  the harness that holds its midpoint (``engine.run``,
  ``client.wait_arrival``, ...).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_trace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: Interval, win: Interval):
    a, b = max(iv[0], win[0]), min(iv[1], win[1])
    return (a, b) if b > a else None


def _device_planes(pd) -> List:
    planes = [p for p in pd.planes if p.name.startswith("/device:")
              and any(line.name in (OPS_LINE, MODULES_LINE)
                      for line in p.lines)]
    return sorted(planes, key=lambda p: p.name)


def _events(plane, line_name: str) -> List[Tuple[float, float, str]]:
    for line in plane.lines:
        if line.name == line_name:
            return [(e.start_ns, e.end_ns, e.name) for e in line.events]
    return []


def _host_spans(pd, names: Sequence[str]) -> List[Tuple[float, float, str]]:
    keep = set(names) | {WINDOW}
    spans = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            spans.extend((e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name in keep)
    return spans


def _name_ops(ops, modules) -> List[Tuple[float, float, str]]:
    """Prefix each op with the program whose execution it starts in."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for a, b, name in ops:
        i = bisect.bisect_right(starts, a) - 1
        prog = modules[i][2] if i >= 0 and modules[i][1] > a else "?"
        out.append((a, b, f"{prog}/{op_name(name)}"))
    return out


def op_name(name: str) -> str:
    """An op's instruction name: a TPU trace names each op by its whole
    HLO text, ``%while.12 = (s32[], ...) while(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_trace(path: str, span_names: Sequence[str] = (),
                 top: int = 10) -> Dict:
    """``{"busy_s", "window_s", "device_ops", "idle_gaps", "devices"}``
    for the trace at ``path``.  Raises when the trace holds no window span
    or no device plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = _host_spans(pd, span_names)
    windows = [(a, b) for a, b, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no host span named {WINDOW!r}")
    win = (min(a for a, _ in windows), max(b for _, b in windows))
    planes = _device_planes(pd)
    if not planes:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} "
                         f"or {MODULES_LINE!r} line")

    busy_per_device = []
    op_time: Dict[str, float] = collections.Counter()
    first_busy: List[Interval] = []
    for k, plane in enumerate(planes):
        ops = []
        modules = _events(plane, MODULES_LINE)
        for a, b, name in (_name_ops(_events(plane, OPS_LINE), modules)
                           or [(a, b, f"{n}/") for a, b, n in modules]):
            c = _clip((a, b), win)
            if c is not None:
                ops.append((c[0], c[1], name))
                op_time[name] += c[1] - c[0]
        busy = union((a, b) for a, b, _ in ops)
        busy_per_device.append(sum(b - a for a, b in busy))
        if k == 0:
            first_busy = busy

    inner = [s for s in spans if s[2] != WINDOW]
    holes = []
    edge = win[0]
    for a, b in first_busy + [(win[1], win[1])]:
        if a > edge:
            holes.append((edge, a))
        edge = max(edge, b)
    holes.sort(key=lambda h: h[0] - h[1])
    gaps = []
    for a, b in holes[:top]:
        mid = (a + b) / 2
        holding = [s for s in inner if s[0] <= mid <= s[1]]
        name = (min(holding, key=lambda s: s[1] - s[0])[2]
                if holding else WINDOW)
        gaps.append([name, (b - a) * 1e-9])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy_per_device) / len(busy_per_device) * 1e-9,
        "window_s": (win[1] - win[0]) * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in ops_sorted],
        "idle_gaps": gaps,
        "devices": len(planes),
    }
