"""The NPB LU cell on the CPU, at 16 ranks and a few planes.

    PYTHONPATH=src python -m pytest perfbench/tests/test_npb_lu_cell.py -q

Covers: the benchmark's copy of the LU op script gives the program's
``lu_builder`` graph job for job, and a short run of ``npb64-lu-sweep``
is correct while the bfloat16 control is not.
"""

import json

import pytest

import _common
from pb import deploy, harness

ROOT = _common.ROOT
CELL = "npb64-lu-sweep"
RANKS, NZ = 16, 5
SEED = 2 ** 33 + 4321           # larger than 32 signed bits hold


def _member_entry():
    cfg = json.loads((ROOT / "perfbench/configs/npb-lu-b-64.json")
                     .read_text())
    (entry,) = cfg["members"]
    return cfg, entry


@pytest.mark.parametrize("iterations", [1, 2])
@pytest.mark.parametrize("seed", [0, 7, 40_000])
def test_op_script_is_the_programs_lu_builder(seed, iterations):
    from repro.core import TraceBuilder, lu_builder

    cfg, entry = _member_entry()
    assert entry["script"] == "npb_lu"
    script = deploy.record("npb_lu", RANKS, cfg["class_scale"], seed,
                           iterations=iterations, nz=NZ)
    ref_jobs = deploy.reference_jobs(script)
    g = lu_builder(RANKS, cfg["class"], iterations=iterations, nz=NZ,
                   seed=seed).build()
    assert len(ref_jobs) == len(g.nodes) == RANKS
    assert sum(len(js) for js in ref_jobs) == len(g.jobs)
    for node, jobs in enumerate(ref_jobs):
        for k, job in enumerate(jobs):
            want = g.jobs[(node, k)]
            assert (job.work, job.cpu_frac) == (want.work, want.cpu_frac)
            assert set(job.deps) == set(want.deps), (node, k)
    # and the replay into the program's builder gives the same graph
    replayed = script.replay(TraceBuilder(RANKS)).build()
    assert replayed.to_text() == g.to_text()


@pytest.fixture(scope="module")
def lu_run(tmp_path_factory):
    """One short run of the LU cell at 16 ranks, with the control."""
    root = _common.small_root(tmp_path_factory.mktemp("lu") / "root", RANKS)
    path = root / "perfbench/configs/npb-lu-b-64.json"
    cfg = json.loads(path.read_text())
    cfg["class_scale"] = 4.0
    cfg["members"] = [dict(m, iterations=1, nz=NZ) for m in cfg["members"]]
    path.write_text(json.dumps(cfg, indent=1))
    return harness.run_cell(CELL, SEED, 1.0, False, root=root,
                            require_chip=False, control=True)


def test_short_run_is_correct(lu_run):
    checks = lu_run["checks"]
    assert lu_run["correct"], checks
    assert lu_run["failed"] == 0 and lu_run["attempted"] >= 4
    for name in ("dmakespan_s", "denergy_rel"):
        assert checks[name]["value"] <= checks[name]["limit"]
    assert checks["narrow_buckets"]["value"] == 0
    assert set(lu_run["metrics"]) == {"sweep_scen_per_s", "setup_s"}


def test_bfloat16_control_is_not_correct(lu_run):
    checks = lu_run["checks"]
    assert not harness.is_correct(harness.control_checks(checks)), \
        checks["control"]
