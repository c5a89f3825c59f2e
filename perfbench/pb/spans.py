"""The program's own spans in a profiler trace (``.xplane.pb``).

The program marks its host work with ``repro.*`` regions
(:func:`repro.obs.trace.region`): ``repro.sweep.run``, ``.plan``,
``.solve``, ``.build``, ``.records`` and ``repro.engine.pack``,
``.dispatch``, ``.wait``, ``.transfer``, ``.results``, each bucket's
carrying its label and rows as args.  For the window the benchmark
marked with its ``bench.window`` span, :func:`reduce_spans` gives

* ``span_self_s``: per ``repro.*`` span name, its seconds in the window
  less the part its child ``repro.*`` spans on the same host thread
  cover;
* ``idle_unattributed_s``: per device, the seconds in the window in
  which the device ran nothing and no ``repro.*`` span was open;
* ``idle_gaps``: the longest idle gaps of the devices, each labelled by
  the innermost ``bench.*`` or ``repro.*`` span that covered the gap's
  midpoint;
* ``span_count``: per ``repro.*`` span name, the spans that start in
  the window;
* ``waves``, ``row_waves``, ``row_slots``: the buckets' wave counts
  (``BucketProfile``), summed over the ``repro.engine.results`` spans
  that start in the window, which carry them as args.

A trace without ``repro.*`` spans (a program that has none) gives an
empty ``span_self_s``, and ``waves`` 0; the readers of these keys then
report nothing.  :func:`pb.tracing.reduce_trace` does not merge these
keys into the reduction the readers see; ``tools/span_report.py`` does.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from .tracing import (BUSY_LINES, TOP, WINDOW_SPAN, Interval, _device_planes,
                      _events, clip, gaps, union)

PROGRAM = "repro."
LABELLED = ("bench.", "repro.")
WAVE_SPAN = "repro.engine.results"
WAVE_ARGS = ("waves", "row_waves", "row_slots")


def overlap(a: List[Interval], b: List[Interval]) -> int:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def self_times(spans: List[Tuple[int, int, str]], lo: int,
               hi: int) -> Dict[str, int]:
    """Per name, the nanoseconds within [lo, hi] of the spans of one
    thread less those of their direct children (spans of one thread
    nest)."""
    out: Dict[str, int] = {}
    stack: List[List] = []            # [end, name, own clipped ns]

    def close(entry):
        out[entry[1]] = out.get(entry[1], 0) + entry[2]

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        seen = max(0, min(b, hi) - max(a, lo))
        if stack:
            stack[-1][2] -= seen
        stack.append([b, name, seen])
    while stack:
        close(stack.pop())
    return out


def _host_events(pd):
    """``(line key, start, end, name, stats)`` of the labelled host
    spans."""
    for p, plane in enumerate(pd.planes):
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(LABELLED):
                    yield ((p, n), int(ev.start_ns), int(ev.end_ns),
                           ev.name, ev.stats)


def reduce_spans(path: str) -> Dict[str, object]:
    """Reduce one ``.xplane.pb`` file; see the module docstring."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    events = list(_host_events(pd))
    windows = [(a, b) for _, a, b, name, _ in events if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = windows[0]

    by_line: Dict[tuple, List[Tuple[int, int, str]]] = {}
    counts = dict.fromkeys(WAVE_ARGS, 0)
    started: Dict[str, int] = {}
    for key, a, b, name, stats in events:
        if not name.startswith(PROGRAM):
            continue
        by_line.setdefault(key, []).append((a, b, name))
        if not lo <= a <= hi:
            continue
        started[name] = started.get(name, 0) + 1
        if name == WAVE_SPAN:
            args = dict(stats)
            for k in WAVE_ARGS:
                counts[k] += int(args.get(k, 0))
    self_ns: Dict[str, int] = {}
    for spans in by_line.values():
        for name, ns in self_times(spans, lo, hi).items():
            self_ns[name] = self_ns.get(name, 0) + ns
    covered = union(clip([s[:2] for spans in by_line.values()
                          for s in spans], lo, hi))

    inner = [(a, b, name) for _, a, b, name, _ in events
             if name != WINDOW_SPAN]
    unattributed: Dict[str, float] = {}
    all_gaps: List[Tuple[int, str]] = []
    for plane in _device_planes(pd):
        busy = union(clip([(int(ev.start_ns), int(ev.end_ns))
                           for ev in _events(plane, BUSY_LINES)], lo, hi))
        idle = gaps(busy, lo, hi)
        unattributed[plane.name] = (sum(b - a for a, b in idle)
                                    - overlap(idle, covered)) * 1e-9
        for a, b in idle:
            mid = (a + b) // 2
            cover = [s for s in inner if s[0] <= mid <= s[1]]
            label = min(cover, key=lambda s: s[1] - s[0])[2] if cover \
                else "none"
            all_gaps.append((b - a, label))
    longest = sorted(all_gaps, key=lambda g: -g[0])[:TOP]
    return dict(
        {"span_self_s": {k: ns * 1e-9 for k, ns in sorted(self_ns.items())},
         "idle_unattributed_s": unattributed,
         "span_count": dict(sorted(started.items())),
         "idle_gaps": [[label, ns * 1e-9] for ns, label in longest]},
        **counts)
