"""The program's ``repro.*`` spans in one traced run of a cell, and the
per-layer metrics that read them (``metrics/build_pct.sweep.py``,
``results_pct.sweep``, ``idle_unattributed_pct.sweep``,
``device_ms_per_wave.sweep``, ``lockstep_idle_pct.sweep``), which the
harness's own trace reduction does not feed yet.

    python3 perfbench/tools/span_report.py --workload npb256-sweep \
        --seed 5401 --seconds 51 [--ranks 8] [--keep trace_out]
    python3 perfbench/tools/span_report.py --workload npb256-sweep \
        --xplane <file.xplane.pb>

The first runs the cell with ``--trace 1`` (cut to ``--ranks`` ranks
when given), keeps its trace while reducing it (under ``--keep`` if
given, else deleted), and prints the run's result with ``spans``, the
reduction of :mod:`pb.spans`, and ``span_metrics``.  The second reduces
a trace already written.  The last line of standard output is JSON.
"""

import argparse
import json
import shutil
import tempfile
from pathlib import Path

import _common

METRICS = ("build_pct.sweep", "results_pct.sweep",
           "idle_unattributed_pct.sweep", "device_ms_per_wave.sweep",
           "lockstep_idle_pct.sweep")


def span_metrics(root, workload, trace):
    """The readers of :data:`METRICS` on ``trace``, the harness's
    reduction with :func:`pb.spans.reduce_spans`'s merged in."""
    from pb import harness

    cell = harness.Cell(root, workload)
    busy = [trace["busy_s"][k] for k in sorted(trace["busy_s"])
            ][:cell.chips]
    ctx = {"layer": {}, "trace": trace, "busy_s": busy, "chips": cell.chips}
    return {m: cell.reader(m).read(ctx) for m in METRICS}


def reduce(path):
    """:func:`pb.tracing.reduce_trace` with :func:`pb.spans.reduce_spans`
    merged in; the accepted gap labels move to ``bench_idle_gaps``."""
    from pb import spans, tracing

    red = tracing.reduce_trace(path)
    red["bench_idle_gaps"] = red.pop("idle_gaps")
    red.update(spans.reduce_spans(path))
    return red


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--xplane")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--ranks", type=int)
    ap.add_argument("--keep")
    args = ap.parse_args()
    from pb import harness, tracing

    root = _common.ROOT
    if args.xplane:
        red = reduce(args.xplane)
        print(json.dumps({"spans": red, "span_metrics": span_metrics(
            root, args.workload, red)}), flush=True)
        return
    if args.ranks:
        root = _common.small_root(
            Path(tempfile.mkdtemp(prefix="span-root-")), args.ranks)
    out = args.keep or tempfile.mkdtemp(prefix="span-trace-")
    res = harness.run_cell(args.workload, args.seed, args.seconds, True,
                           root=root, keep_trace=out)
    res["spans"] = reduce(tracing.find_xplane(out))
    if not args.keep:
        shutil.rmtree(out, ignore_errors=True)
    res["span_metrics"] = span_metrics(root, args.workload, res["spans"])
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
