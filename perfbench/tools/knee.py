"""Find a serving cell's knee: offer its traffic at several fixed rates
in one process and print, per rate, the latency tails, the completed
rate and whether the backlog grows.

    python3 perfbench/tools/knee.py --workload npb64-serve --seed 5 \
        --seconds 30 --rates 1,2,3,4

The knee is the highest rate whose completed rate keeps up with the
offered one and whose latency does not grow over the window (last
third's median over the first third's).  Each rate draws its own
bounds (seed + its index), so the one service's result cache never
answers a request from an earlier rate.  Runs on the chip only.
"""

import argparse
import statistics
import time

import _common  # noqa: F401  (paths)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    from pb import harness
    from pb.clock import Clock

    cell = harness.Cell(_common.ROOT, args.workload)
    harness.enable_cache(_common.ROOT)
    clock = Clock()
    print(harness.device_info(cell.chips, True), flush=True)
    run = harness.Run(cell, args.seed, args.seconds, clock)
    state = cell.generator.setup(run)
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            run.traffic = dict(cell.traffic, rate_hz=rate)
            run.seed = args.seed + k
            t0 = time.perf_counter()
            win = cell.generator.window(run, state)
            lat = win["layer"]["latency_s"]
            third = max(1, len(lat) // 3)
            growth = statistics.median(lat[-third:]) / \
                statistics.median(lat[:third])
            print(f"[knee] rate {rate}: requests {len(lat)} failed "
                  f"{win['failed']} cache hits {win['layer']['cache_hits']} "
                  f"done/s "
                  f"{win['e2e']['serve_done_per_s']:.4f} p50 "
                  f"{harness.nearest_rank(lat, 50):.4f} p90 "
                  f"{win['e2e']['serve_p90_s']:.4f} growth {growth:.3f} "
                  f"buckets {win['layer']['buckets']} wall "
                  f"{time.perf_counter() - t0:.1f}", flush=True)
    finally:
        cell.generator.close(state)


if __name__ == "__main__":
    main()
