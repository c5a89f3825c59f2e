"""Readings that set a cell's limits: the program's numbers and the
control's (the bfloat16 reference in the program's place), on many
seeds in one process.

    python3 perfbench/tools/readings.py --workload npb256-sweep \
        --seconds 1 --seeds 101,102,103

Each seed is one run of the cell (a short window: one pass, or a few
seconds of requests) with ``control`` on.  It prints one line per seed,
with the verdict of ``is_correct`` on the program's numbers and on the
control's put in their place, and, at the end, the largest program
reading and the smallest control reading of each number.  Runs on the
chip only.
"""

import argparse
import json

import _common  # noqa: F401  (paths)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    from pb import harness
    from pb.clock import Clock

    clock = Clock()
    lows, highs = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               root=_common.ROOT, control=True, clock=clock)
        checks = res["checks"]
        ctl_correct = harness.is_correct(harness.control_checks(checks))
        ctl = checks.pop("control")
        prog = {k: c["value"] for k, c in checks.items()}
        print(f"[readings] seed {seed} correct {res['correct']} program "
              f"{json.dumps(prog)} control correct {ctl_correct} "
              f"{json.dumps(ctl)}", flush=True)
        for k, v in prog.items():
            highs[k] = max(highs.get(k, v), v)
        for k, v in ctl.items():
            lows[k] = min(lows.get(k, v), v)
    print(f"[readings] program largest {json.dumps(highs)}", flush=True)
    print(f"[readings] control smallest {json.dumps(lows)}", flush=True)


if __name__ == "__main__":
    main()
