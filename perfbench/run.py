"""Run one cell of the benchmark, as ``BENCHMARK.json`` names it:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout that holds the program (``src/``).  It
measures on a TPU only: with no TPU, or fewer chips than the cell asks
for, it exits 1 and prints no result.  The last line of standard output
is the result as one JSON object.
"""

import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
for p in (str(BENCH_DIR), str(BENCH_DIR.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

if __name__ == "__main__":
    from pb import harness

    sys.exit(harness.main(t_start=T_START))
