"""Shared start-up of the benchmark's tools: paths, and a copy of the
checkout's benchmark with one configuration cut to fewer ranks."""

import json
import shutil
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_root(dest: Path, ranks: int, scale: float = 1.0) -> Path:
    """A benchmark root at ``dest`` whose configurations hold ``ranks``
    ranks at problem scale ``scale``: for traces and tests that must be
    small.  Traffic, metrics and generators are the checkout's."""
    dest = Path(dest)
    shutil.rmtree(dest, ignore_errors=True)
    (dest / "perfbench").mkdir(parents=True)
    for d in ("traffic", "metrics", "configs"):
        shutil.copytree(BENCH_DIR / d, dest / "perfbench" / d)
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for path in (dest / "perfbench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["ranks"], cfg["class_scale"] = ranks, scale
        path.write_text(json.dumps(cfg, indent=1))
    return dest
