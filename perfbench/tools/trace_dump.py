"""Print the planes, lines and event counts of an ``.xplane.pb`` file,
with the first events of each line: to look at a trace by hand.

    python3 perfbench/tools/trace_dump.py <file.xplane.pb> [events]
"""

import sys

from jax.profiler import ProfileData


def main():
    path = sys.argv[1]
    show = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            span = (f"{events[0].start_ns:.0f}..{events[-1].end_ns:.0f}"
                    if events else "-")
            print(f"  line {line.name!r}: {len(events)} events, {span}")
            for ev in events[:show]:
                print(f"    {ev.name!r} start {ev.start_ns:.0f} "
                      f"dur {ev.duration_ns:.0f}")


if __name__ == "__main__":
    main()
