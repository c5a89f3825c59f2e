"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

It reads the trace with ``jax.profiler.ProfileData`` and returns, for
the window the benchmark marked with its ``bench.window`` host span:

* per device, the seconds in which a program ran (the union of the
  intervals of the events on its ``XLA Modules`` line, or on its
  ``XLA Ops`` line where it has no modules line), clipped to the
  window.  The modules line is used because the profiler stops
  recording operations once its buffer is full, which a while loop of
  thousands of waves reaches within seconds, while the few module
  events are all kept;
* the device operations that took the most time, summed by HLO name
  (the text before `` = ``), from the ``XLA Ops`` line;
* the longest idle gaps of the devices, each labelled by the innermost
  ``bench.*`` host span that covered the gap's midpoint.

A trace in which no device plane is found gives no device numbers; the
readers that need them then report nothing.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
BUSY_LINES = ("XLA Modules", "XLA Ops")
OP_LINES = ("XLA Ops", "XLA Modules")
TOP = 10

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace dir."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping ``(start, end)`` intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of merged ``busy`` intervals within [lo, hi]."""
    out, reach = [], lo
    for a, b in busy:
        if a > reach:
            out.append((reach, a))
        reach = max(reach, b)
    if hi > reach:
        out.append((reach, hi))
    return out


def _device_planes(pd) -> List:
    planes = [p for p in pd.planes if p.name.startswith("/device:")
              and not p.name.startswith("/device:CUSTOM")]
    return sorted(planes, key=lambda p: p.name)


def _events(plane, names) -> List:
    lines = {line.name: line for line in plane.lines}
    for name in names:
        if name in lines:
            return list(lines[name].events)
    return []


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _host_spans(pd) -> List[Tuple[int, int, str]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((int(ev.start_ns), int(ev.end_ns), ev.name))
    return spans


def reduce_trace(path: str) -> Dict[str, object]:
    """Reduce one ``.xplane.pb`` file; see the module docstring.

    Returns ``{"window_s", "busy_s" (device name -> s), "top_ops"
    [[name, s], ...], "idle_gaps" [[label, s], ...]}``; ``busy_s`` is
    empty where the trace has no device plane.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = _host_spans(pd)
    windows = [(a, b) for a, b, name in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = windows[0]
    inner = [s for s in spans if s[2] != WINDOW_SPAN]
    busy_s: Dict[str, float] = {}
    op_time: Dict[str, float] = {}
    all_gaps: List[Tuple[int, str]] = []
    for plane in _device_planes(pd):
        for ev in _events(plane, OP_LINES):
            seen = min(int(ev.end_ns), hi) - max(int(ev.start_ns), lo)
            if seen > 0:
                name = op_name(ev.name)
                op_time[name] = op_time.get(name, 0.0) + seen * 1e-9
        busy = union(clip([(int(ev.start_ns), int(ev.end_ns))
                           for ev in _events(plane, BUSY_LINES)], lo, hi))
        busy_s[plane.name] = sum(b - a for a, b in busy) * 1e-9
        for a, b in gaps(busy, lo, hi):
            mid = (a + b) // 2
            cover = [s for s in inner if s[0] <= mid <= s[1]]
            label = min(cover, key=lambda s: s[1] - s[0])[2] if cover \
                else "none"
            all_gaps.append((b - a, label))
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(all_gaps, key=lambda g: -g[0])[:TOP]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_s,
            "top_ops": [[name, s] for name, s in top],
            "idle_gaps": [[label, ns * 1e-9] for ns, label in longest]}
