"""The benchmark's own tests run on the CPU, at a few ranks."""

import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(BENCH_DIR / "tools"), str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
