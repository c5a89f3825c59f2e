"""Record one small traced run of a cell and keep its trace: the test
data of the trace reduction, and a trace to look at by hand.

    python3 perfbench/tools/record_trace.py --workload npb256-sweep \
        --ranks 8 --seconds 1 --out trace_out

The cell's configuration is cut to ``--ranks`` ranks at problem scale
1 in a copy of the benchmark under ``<out>/root``.
"""

import argparse
import json
from pathlib import Path

import _common  # noqa: F401  (paths)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ranks", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from pb import harness

    out = Path(args.out).resolve()
    root = _common.small_root(out / "root", args.ranks)
    res = harness.run_cell(args.workload, 1, args.seconds, True, root=root,
                           keep_trace=str(out / "trace"))
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
