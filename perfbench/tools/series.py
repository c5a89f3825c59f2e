"""Run a cell several times, each run a process of its own, and print
the spread of each end-to-end metric: what a cell's bounds are set from.

    python3 perfbench/tools/series.py --workload npb256-sweep \
        --seconds 40 --seeds 11,12,13,14,15,16 --sets 2 \
        --trace-seeds 21,22,23 --out series_out

Each set runs every seed once, in order (the sets use the same seeds).
The spread of a metric is the distance between its first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the
median.  This process never imports JAX, so each run gets the chips.
"""

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
RUN_TIMEOUT_S = 1200


def one_run(workload, seed, seconds, trace, out: Path, tag: str):
    t0 = time.perf_counter()
    stem = out / f"{workload}.{tag}.s{seed}.t{trace}"
    with open(f"{stem}.out", "w") as so, open(f"{stem}.err", "w") as se:
        try:
            rc = subprocess.run(
                [sys.executable, str(BENCH_DIR / "run.py"), "--workload",
                 workload, "--seed", str(seed), "--seconds", str(seconds),
                 "--trace", str(trace)], cwd=ROOT, stdout=so, stderr=se,
                timeout=RUN_TIMEOUT_S).returncode
        except subprocess.TimeoutExpired:
            rc = 124
    wall = time.perf_counter() - t0
    lines = Path(f"{stem}.out").read_text().strip().splitlines()
    res = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, wall, res


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("nan")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        values = {}
        for seed in seeds:
            rc, wall, res = one_run(args.workload, seed, args.seconds, 0,
                                    out, f"set{k + 1}")
            summary = {"set": k + 1, "seed": seed, "rc": rc,
                       "wall_s": round(wall, 1)}
            if res:
                summary["correct"] = res["correct"]
                summary["metrics"] = {m: v["value"]
                                      for m, v in res["metrics"].items()}
                summary["checks"] = {c: v["value"]
                                     for c, v in res["checks"].items()}
                summary["memory_peak_bytes"] = \
                    res["device"]["memory_peak_bytes"]
                for m, v in summary["metrics"].items():
                    values.setdefault(m, []).append(v)
            print(f"[series] {json.dumps(summary)}", flush=True)
        sets.append(values)
        for m, vs in values.items():
            if len(vs) >= 2:
                print(f"[series] set {k + 1} {m}: median "
                      f"{statistics.median(vs)!r} spread {spread(vs)!r}",
                      flush=True)
    for seed in (int(s) for s in args.trace_seeds.split(",") if s):
        rc, wall, res = one_run(args.workload, seed, args.seconds, 1, out,
                                "traced")
        summary = {"trace": 1, "seed": seed, "rc": rc,
                   "wall_s": round(wall, 1)}
        if res:
            summary.update(correct=res["correct"], device=res["device"],
                           metrics={m: v["value"]
                                    for m, v in res["metrics"].items()},
                           breakdown=res.get("breakdown"))
        print(f"[series] {json.dumps(summary)}", flush=True)


if __name__ == "__main__":
    main()
