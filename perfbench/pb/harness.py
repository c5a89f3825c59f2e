"""Runs one cell of the benchmark once, as ``BENCHMARK.json`` names it.

A cell names a configuration (``configs[].file``) and a traffic mix
(``traffic/<name>.json``); the traffic file names its generator
(``pb/generators/<generator>.py``), and each per-layer metric is read
by ``metrics/<name>.py``.  Nothing here knows a cell, a configuration
or a metric by name: a new one is new files and entries.

A run: check the device, build the deployment and warm up every shape
(set-up), measure the window, read the device's peak memory, free the
program's state, then compare a seeded sample of what the window
resolved with the plain reference (:mod:`pb.reference`) in spawned
processes that never load JAX.  The last line of standard output is
the result; the numbers compared are printed beside their limits as
the last lines of standard error and under ``checks``, the last key of
the result.

This module imports neither JAX nor the program at import time: the
reference's worker processes import it.
"""

from __future__ import annotations

import concurrent.futures as futures
import contextlib
import gc
import json
import math
import multiprocessing
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

from . import deploy, reference

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """The run cannot go on: wrong device, missing file, failed phase."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def nearest_rank(values: List[float], pct: float) -> float:
    """Nearest-rank percentile; ``inf`` entries count as missing."""
    ordered = sorted(values)
    return ordered[max(1, math.ceil(pct / 100 * len(ordered))) - 1]


# ------------------------------------------------------------ the registry
def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"no such benchmark file: {path}")
    return json.loads(path.read_text())


class Cell:
    """One entry of ``workloads`` with its files resolved by name."""

    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        self.bench = load_json(self.root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.cfg = load_json(self.root / configs[self.spec["config"]]["file"])
        self.traffic = load_json(self.root / "perfbench" / "traffic"
                                 / f"{self.spec['traffic']}.json")
        self.generator = deploy.load_module(
            BENCH_DIR / "pb" / "generators"
            / f"{self.traffic['generator']}.py",
            f"gen_{self.traffic['generator']}")
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in self.bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in reported)]

    def reader(self, metric: str):
        """The per-layer metric's reader, ``metrics/<name>.py``."""
        return deploy.load_module(self.root / "perfbench" / "metrics"
                                  / f"{metric}.py", f"metric_{metric}")


class Run:
    """What a generator sees: the cell, the seed, the clock, spans."""

    def __init__(self, cell: Cell, seed: int, seconds: float, clock):
        self.cell = cell
        self.cfg = cell.cfg
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.clock = clock

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span on the profiler's clock (cheap when not tracing)."""
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield


# ----------------------------------------------------------------- device
def device_info(chips: int, require_chip: bool) -> dict:
    """What JAX found; :class:`BenchError` unless it is enough TPUs."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX found platform {dev.platform!r}; "
                         f"the benchmark measures on the chip only")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def enable_cache(root: Path) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout,
    handed to the program, with every program kept."""
    path = str(Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.backends.jax import enable_compile_cache

    return enable_compile_cache()


# ------------------------------------------------------------- comparison
def _ref_worker(scn: reference.RefScenario, dtype: str):
    out = reference.simulate(scn, dtype)
    out["jax_loaded"] = "jax" in sys.modules
    return out


def run_references(scns, dtype: str) -> List[dict]:
    """The reference over ``scns`` in spawned processes, longest first."""
    order = sorted(range(len(scns)),
                   key=lambda k: -sum(len(j) for j in scns[k].jobs))
    workers = max(1, min(len(scns), os.cpu_count() or 1))
    out: List[Optional[dict]] = [None] * len(scns)
    ctx = multiprocessing.get_context("spawn")
    with futures.ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        futs = {pool.submit(_ref_worker, scns[k], dtype): k for k in order}
        for fut in futures.as_completed(futs):
            out[futs[fut]] = fut.result()
    if any(r["jax_loaded"] for r in out):
        raise BenchError("a reference worker loaded JAX")
    return out


def gaps(items, refs) -> Dict[str, float]:
    """The widest gaps of ``items`` (label, scenario, makespan, energy)
    from the reference's results ``refs``."""
    dm = max(abs(it[2] - r["makespan"]) for it, r in zip(items, refs))
    de = max(abs(it[3] - r["energy"]) / max(r["energy"], 1e-12)
             for it, r in zip(items, refs))
    return {"dmakespan_s": dm, "denergy_rel": de}


def compare(items, limits: dict, exact: Dict[str, int],
            control: bool = False) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each beside its limit.

    ``items`` are ``(label, RefScenario, makespan_s, energy_j)`` of the
    program; ``exact`` are counts that must be 0 (requests that never
    resolved, say).  With ``control`` the bfloat16 reference is also put
    in the program's place, and its numbers are returned under
    ``control``.
    """
    checks = {k: {"value": v, "limit": 0} for k, v in exact.items()}
    if items:
        scns = [it[1] for it in items]
        t0 = time.perf_counter()
        refs = run_references(scns, "float64")
        log(f"reference: {len(items)} scenarios in "
            f"{time.perf_counter() - t0:.3f} s")
        for k, v in gaps(items, refs).items():
            checks[k] = {"value": v, "limit": limits[k]}
        if control:
            lows = run_references(scns, "bfloat16")
            ctl = [(it[0], it[1], lo["makespan"], lo["energy"])
                   for it, lo in zip(items, lows)]
            checks["control"] = gaps(ctl, refs)
    else:
        checks["compared"] = {"value": 0, "limit": 1}
    return checks


def control_checks(checks: Dict[str, dict]) -> Dict[str, dict]:
    """``checks`` with the control's numbers put in the program's place:
    what :func:`is_correct` has to judge not correct."""
    out = {k: dict(c) for k, c in checks.items() if k != "control"}
    for k, v in checks["control"].items():
        out[k]["value"] = v
    return out


def is_correct(checks: Dict[str, dict]) -> bool:
    ok = True
    for name, c in checks.items():
        if name == "control":
            continue
        if name == "compared":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


# -------------------------------------------------------------------- run
def read_layers(cell: Cell, ctx: dict) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, t_start: Optional[float] = None,
             require_chip: bool = True, control: bool = False,
             clock=None, keep_trace: Optional[str] = None) -> dict:
    """One run of one cell; returns the result object (see module doc).

    ``require_chip=False`` skips the look for a TPU (tests on the CPU).
    ``control`` adds the bfloat16 reference's numbers to ``checks``.
    ``keep_trace`` traces into that directory and leaves the trace there.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(root, workload)
    cache = enable_cache(root)
    if clock is None:
        from .clock import Clock

        clock = Clock()
    device = device_info(cell.chips, require_chip)
    log(f"cell {workload}: config {cell.spec['config']}, traffic "
        f"{cell.spec['traffic']}, chips {cell.chips}; device {device}; "
        f"compile cache {cache}")
    if trace:
        # A traced run measures a short window of its own: the profiler
        # keeps every device operation, so a whole window's trace would
        # take minutes to write and to read.
        seconds = min(seconds, float(cell.traffic["trace_seconds"]))
    run = Run(cell, seed, seconds, clock)
    gen = cell.generator
    state = gen.setup(run)
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    setup_compile_s = clock.compile_seconds(t_start, t_window)
    log(f"set-up {setup_s:.3f} s, of it compiling {setup_compile_s:.3f} s, "
        f"{clock.cache_hits(t_start, t_window)} persistent-cache hits")

    trace_dir = keep_trace or (tempfile.mkdtemp(prefix="bench-trace-")
                               if trace else None)
    if trace:
        import jax

        jax.profiler.start_trace(trace_dir)
    try:
        with run.span("bench.window"):
            win = gen.window(run, state)
    finally:
        if trace:
            import jax

            jax.profiler.stop_trace()
    w0, w1 = win["window"]
    log(f"window {w1 - w0:.3f} s: {win['attempted']} attempted, "
        f"{win['failed']} failed; compile events in the window "
        f"{clock.compiles(w0, w1)} ({clock.compile_seconds(w0, w1):.3f} s)")
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    gen.close(state)
    del state
    gc.collect()

    reduced = None
    if trace:
        from . import tracing

        t0 = time.perf_counter()
        path = tracing.find_xplane(trace_dir)
        if path is None:
            raise BenchError("the profiler wrote no trace")
        size = os.path.getsize(path)
        reduced = tracing.reduce_trace(path)
        if not keep_trace:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace {size} bytes reduced in "
            f"{time.perf_counter() - t0:.3f} s: window "
            f"{reduced['window_s']:.3f} s, busy {reduced['busy_s']}")

    checks = compare(win["items"], cell.cfg["limits"], win["exact"],
                     control)
    result = {"correct": is_correct(checks),
              "attempted": win["attempted"], "failed": win["failed"]}
    if trace:
        busy = [reduced["busy_s"][k] for k in sorted(reduced["busy_s"])
                ][:cell.chips]
        ctx = {"layer": win["layer"], "trace": reduced,
               "busy_s": busy, "setup_compile_s": setup_compile_s,
               "chips": cell.chips}
        result["metrics"] = read_layers(cell, ctx)
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = reduced["window_s"]
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = dict(win["e2e"], setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise BenchError(f"the generator gave no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = checks
    return result


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        if name == "control":
            log(f"control (bfloat16 reference): {c}; correct "
                f"{is_correct(control_checks(checks))}")
            continue
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start)
    except Exception:  # noqa: BLE001 — the command's boundary: report, fail
        import traceback

        traceback.print_exc()
        log("FAILED: no result")
        return 1
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0
