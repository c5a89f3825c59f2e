"""The benchmark harness on the CPU, at a few ranks.

    PYTHONPATH=src python -m pytest perfbench/tests -q

Covers: files found by name (and a configuration, a traffic mix and a
per-layer metric added as files only), deterministic generators, the
copied NPB op scripts against the program's ``npb_family``, the plain
reference against the program's event simulator, open-loop timing from
the due time, the refusal without a TPU, the trace reduction on a
recorded chip trace, and ``correct`` coming out false when the timed
path is broken underneath.
"""

import json
import os
import random
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import _common
import faults
from pb import deploy, harness, reference, tracing
from pb.generators import open_loop, sweep_grid

ROOT = _common.ROOT
DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 33 + 12345          # larger than 32 signed bits hold


def add_entries(root, entries):
    """Add ``BENCHMARK.json`` entries (configs, workloads, metrics) to
    the benchmark at ``root``, as a later change would."""
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for key, items in entries.items():
        bench[key].extend(items)
    path.write_text(json.dumps(bench))


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A copy of the benchmark with every configuration at 4 ranks, and
    the serve cell (``data/serve_cell.json``: its configuration, traffic,
    generator and readers are files of the benchmark)."""
    root = _common.small_root(tmp_path_factory.mktemp("bench") / "root", 4)
    add_entries(root, json.loads((DATA / "serve_cell.json").read_text()))
    return root


def run(root, workload, seed=SEED, seconds=1.0, trace=False, **kw):
    return harness.run_cell(workload, seed, seconds, trace, root=root,
                            require_chip=False, **kw)


# ------------------------------------------------------------ by name
@pytest.mark.parametrize("root", ["checkout", "small"])
def test_cells_resolve_by_name(request, root):
    root = ROOT if root == "checkout" else request.getfixturevalue("small")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.Cell(root, w["name"])
        assert cell.cfg["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_files_added_alone_are_picked_up(small, tmp_path):
    root = tmp_path / "root"
    shutil.copytree(small, root)
    cfg = json.loads((root / "perfbench/configs/npb-b-64.json").read_text())
    cfg.update(name="npb-new", ranks=3)
    (root / "perfbench/configs/npb-new.json").write_text(json.dumps(cfg))
    (root / "perfbench/traffic/sweep-1bound.json").write_text(json.dumps(
        {"generator": "sweep_grid", "bounds_per_group": 1,
         "bound_frac": [0.3, 0.6], "check_per_group": 1,
         "trace_seconds": 0.3}))
    (root / "perfbench/metrics/scen_count.sweep.py").write_text(
        "def read(ctx):\n    return ctx['layer'].get('scenarios')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="npb-new",
                                 file="perfbench/configs/npb-new.json"))
    bench["workloads"].append({"name": "new-sweep", "config": "npb-new",
                               "traffic": "sweep-1bound", "chips": 1,
                               "why": "added as files"})
    for m in bench["end_to_end"]:
        if m["name"] == "sweep_scen_per_s":
            m["workloads"].append("new-sweep")
    bench["per_layer"].append({"name": "scen_count.sweep", "unit": "1",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "sweep planner",
                               "moves": "sweep_scen_per_s",
                               "workloads": ["new-sweep"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    plain = run(root, "new-sweep", seconds=0.3)
    assert plain["correct"], plain["checks"]
    assert set(plain["metrics"]) == {"sweep_scen_per_s", "setup_s"}
    traced = run(root, "new-sweep", seconds=0.3, trace=True)
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["scen_count.sweep"]["value"] >= 4
    assert "compile_s" in traced["metrics"]
    assert list(traced)[-1] == "checks"


# ------------------------------------------------------- determinism
def _cell_run(root, workload, seed, seconds):
    cell = harness.Cell(root, workload)
    return harness.Run(cell, seed, seconds, clock=None)


def test_generators_are_deterministic_per_seed(small):
    sched = [open_loop.schedule(_cell_run(small, "npb64-serve", s, 5.0), 2)
             for s in (SEED, SEED, SEED + 1)]
    assert sched[0] == sched[1] != sched[2]
    # the same arrivals and sizes for every seed; the seed draws bounds
    assert [s[:3] for s in sched[0]] == [s[:3] for s in sched[2]]
    assert [s[3] for s in sched[0]] != [s[3] for s in sched[2]]
    mix = [(i, p) for _, i, p, _ in sched[0]]
    counts = [mix.count(k) for k in set(mix)]
    assert max(counts) - min(counts) <= 1
    r = _cell_run(small, "npb256-sweep", SEED, 1.0)
    fr = [sweep_grid._fracs(r, random.Random(f"x/{s}"), 2)
          for s in (SEED, SEED, SEED + 1)]
    assert fr[0] == fr[1] != fr[2]
    cfg = r.cfg
    d = [[deploy.member_digest(m) for m in deploy.build_deployment(cfg, s)]
         for s in (SEED, SEED, SEED + 1)]
    assert d[0] == d[1] != d[2]


class _PassClock:
    """A clock that moves only when the engine runs a pass."""

    def __init__(self, pass_s):
        self.now, self.pass_s = 0.0, pass_s

    def perf_counter(self):
        return self.now

    def wrap(self, run):
        def timed(grid):
            self.now += self.pass_s
            return run(grid)
        return timed


@pytest.mark.parametrize("seconds,passes", [(1.0, 3), (0.2, 1)])
def test_sweep_window_is_whole_passes_within_its_seconds(small, monkeypatch,
                                                         seconds, passes):
    r = _cell_run(small, "npb256-sweep", SEED, seconds)
    state = sweep_grid.setup(r)
    clock = _PassClock(0.3)
    monkeypatch.setattr(sweep_grid, "time", clock)
    monkeypatch.setattr(state["engine"], "run", clock.wrap(state["engine"].run))
    try:
        win = sweep_grid.window(r, state)
    finally:
        sweep_grid.close(state)
    per_pass = 2 * 2 * r.traffic["bounds_per_group"]
    assert win["attempted"] == passes * per_pass
    t0, t1 = win["window"]
    assert t1 - t0 == pytest.approx(passes * 0.3)


def test_npb_scripts_match_npb_family():
    """The copied op scripts and node draws give the scenarios the
    program's ``npb_family`` gives today, digest for digest."""
    from repro.core import npb_family
    from repro.core.sweep import specs_signature

    cfg = json.loads((ROOT / "perfbench/configs/npb-b-64.json").read_text())
    homo = dict(cfg, cluster={"pattern": [["arndale-5410", 1.0]],
                              "speed_jitter": 0.0})
    rng = random.Random(0)
    ours = []
    for name in ("npb_is", "npb_ep", "npb_cg"):
        gseed = rng.randrange(1 << 16)
        mixed = rng.random() >= 0.5
        m = deploy.build_member(cfg if mixed else homo,
                                {"name": name, "script": name}, gseed,
                                rng.randrange(1 << 16) if mixed else 0)
        ours.append((deploy.digest([m.graph.to_text()]),
                     specs_signature(m.specs)))
    fam = npb_family(0, klass="B", nodes=(64,))
    theirs = [(deploy.digest([m.graph.to_text()]), specs_signature(m.specs))
              for m in fam.members]
    assert ours == theirs


def test_reference_matches_the_event_simulator():
    from repro.core import simulate

    cfg = json.loads((ROOT / "perfbench/configs/npb-b-64.json").read_text())
    cfg = dict(cfg, ranks=6)
    for name in ("npb_is", "npb_ep", "npb_cg"):
        m = deploy.build_member(cfg, {"name": name, "script": name}, 7, 8)
        for policy in ("equal-share", "oracle"):
            b = m.bound(0.35)
            want = simulate(m.graph, list(m.specs), b, policy=policy,
                            trace_every=None)
            got = reference.simulate(deploy.ref_scenario(m, b, policy))
            assert abs(got["makespan"] - want.makespan) < 1e-9
            assert abs(got["energy"] - want.energy_j) < 1e-9 * want.energy_j


# ------------------------------------------------------ open-loop timing
class _Stalled:
    """A service whose second submit stalls: every request due during
    the stall must show it in its latency."""

    class Ticket:
        def __init__(self, rec):
            self.rec = rec

        def result(self, timeout=None):
            return self.rec

    class Stats:
        phantom_rows = 0

    def __init__(self, stall_s):
        self.stall_s = stall_s
        self.n = 0
        self.profile = type("P", (), {"buckets": []})()

    def stats(self):
        return self.Stats()

    def submit(self, s):
        self.n += 1
        if self.n == 2:
            time.sleep(self.stall_s)
        res = type("R", (), {"makespan": 1.0, "energy_j": 1.0})()
        rec = type("Rec", (), {"ok": True, "latency_s": 0.001,
                               "cached": False, "result": res})()
        return self.Ticket(rec)


def test_open_loop_times_from_the_due_time(small):
    r = _cell_run(small, "npb64-serve", SEED, 1.0)
    r.traffic = dict(r.traffic, rate_hz=20.0)
    r.span = lambda name: __import__("contextlib").nullcontext()
    members = deploy.build_deployment(r.cfg, SEED)
    stall = 0.5
    win = open_loop.window(r, {"members": members, "svc": _Stalled(stall)})
    plan = open_loop.schedule(r, len(members))
    lat = win["layer"]["latency_s"]
    late = win["layer"]["late_s"]
    stall_end = plan[1][0] + stall
    behind = [k for k in range(2, len(plan)) if plan[k][0] < stall_end]
    assert len(behind) >= 3
    for k in behind:
        assert lat[k] >= stall_end - plan[k][0] - 0.02
        assert late[k] >= stall_end - plan[k][0] - 0.02
    assert win["layer"]["requests"] == len(plan)


# ------------------------------------------------------- refusal
def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "npb256-sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_exits_nonzero_without_a_tpu(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "src", root / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    proc = _run_py(root)
    assert _no_result(proc), proc.stdout[-2000:]
    assert "no TPU" in proc.stderr


def test_exits_nonzero_with_the_benchmark_alone(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    assert _no_result(_run_py(root))


# ------------------------------------------------------ trace reduction
def test_trace_reduction_on_a_recorded_chip_trace():
    path = DATA / "small.xplane.pb"
    red = tracing.reduce_trace(str(path))
    assert red["window_s"] > 0
    assert red["busy_s"], "no device plane found"
    for busy in red["busy_s"].values():
        assert 0 < busy <= red["window_s"]
    assert red["top_ops"] and all(s > 0 for _, s in red["top_ops"])
    assert len(red["top_ops"]) <= tracing.TOP
    labels = {label for label, _ in red["idle_gaps"]}
    assert labels and labels <= {"none"} | {
        "bench.grid", "bench.sweep_run", "bench.submit", "bench.sleep",
        "bench.wait"}


def test_interval_arithmetic():
    busy = tracing.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tracing.gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert tracing.clip(busy, 2, 6) == [(2, 3), (5, 6)]


# ------------------------------------------------ control and faults
@pytest.mark.parametrize("workload", ["npb256-sweep", "npb64-serve"])
def test_program_passes_and_control_fails(small, workload):
    res = run(small, workload, seconds=1.0, control=True)
    checks = res["checks"]
    assert res["correct"], checks
    for name in ("dmakespan_s", "denergy_rel"):
        assert checks[name]["value"] <= checks[name]["limit"]
    assert not harness.is_correct(harness.control_checks(checks)), \
        checks["control"]


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("workload", ["npb256-sweep", "npb64-serve"])
def test_broken_timed_path_is_not_correct(small, monkeypatch, workload,
                                          fault):
    from repro.backends.jax import engine

    monkeypatch.setattr(engine, "_device_get", faults.broken(fault))
    if workload == "npb64-serve":
        # several requests per bucket, so rows differ within a bucket
        tr = small / "perfbench/traffic/poisson-fresh.json"
        saved = tr.read_text()
        tr.write_text(json.dumps(dict(json.loads(saved), rate_hz=60.0)))
        try:
            res = run(small, workload, seconds=1.0)
        finally:
            tr.write_text(saved)
    else:
        res = run(small, workload, seconds=0.5)
    assert not res["correct"], res["checks"]
