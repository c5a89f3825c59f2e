"""NPB LU's SSOR wavefront (``lu_builder``/``lu_like``): NPB's process
grid and partition, the point-to-point dependencies of each sweep, and
the compiled sweep held to the event simulator on it."""

import pytest

from repro.core import (Scenario, SweepEngine, heterogeneous_cluster,
                        is_like, lu_builder, lu_like,
                        max_useful_cluster_bound,
                        min_feasible_cluster_bound, simulate)
from repro.core.workloads import LU_GRID, lu_extent, lu_proc_grid

RANKS, NZ = 16, 6


def _lane(g, node):
    return sorted((jid for jid in g.jobs if jid[0] == node),
                  key=lambda jid: jid[1])


def _neighbours(n_nodes):
    """Each rank's ``{side: rank}`` on NPB's grid (``neighbors.f``)."""
    xdim, ydim = lu_proc_grid(n_nodes)
    out = []
    for r in range(n_nodes):
        row, col = r % xdim, r // xdim
        sides = {"n": (row > 0, r - 1), "s": (row < xdim - 1, r + 1),
                 "w": (col > 0, r - xdim), "e": (col < ydim - 1, r + xdim)}
        out.append({k: v for k, (ok, v) in sides.items() if ok})
    return out


def _lane_len(n_nodes, nz, iterations):
    """Jobs on each lane, counted from NPB LU's order of operations: a
    compute is a job; an op ends the lane's last job, or opens a
    zero-work marker when that job already ends in an op (or there is
    none); a lane ending in an op gets a terminal job."""
    out = []
    for nb in _neighbours(n_nodes):
        ops = []
        for _ in range(iterations):
            for recv, send in (("nw", "se"), ("se", "nw")):
                for _k in range(nz - 2):
                    ops += [s for s in recv if s in nb] + ["compute"] \
                        + [s for s in send if s in nb]
            ops += [s for s in "nswe" if s in nb] * 2 + ["compute"]
        ops.append("allreduce")
        jobs, ended = 0, True
        for op in ops:
            if op == "compute":
                jobs, ended = jobs + 1, False
            else:
                jobs, ended = jobs + ended, True
        out.append(jobs + ended)
    return out


# ------------------------------------------------------------- structure
@pytest.mark.parametrize("n, want", [(1, (1, 1)), (2, (2, 1)), (8, (4, 2)),
                                     (16, (4, 4)), (32, (8, 4)),
                                     (64, (8, 8)), (256, (16, 16))])
def test_proc_grid_is_npbs(n, want):
    assert lu_proc_grid(n) == want


@pytest.mark.parametrize("n", [0, 3, 12, 48])
def test_proc_grid_needs_a_power_of_two(n):
    with pytest.raises(ValueError, match="power of two"):
        lu_proc_grid(n)


@pytest.mark.parametrize("parts, want", [(8, [13] * 6 + [12] * 2),
                                         (4, [26, 26, 25, 25]),
                                         (16, [7] * 6 + [6] * 10)])
def test_partition_is_npbs(parts, want):
    got = [lu_extent(LU_GRID, parts, i) for i in range(parts)]
    assert got == want and sum(got) == LU_GRID


@pytest.mark.parametrize("n, nz, iterations", [(RANKS, NZ, 1),
                                               (RANKS, NZ, 2), (4, 5, 1),
                                               (64, LU_GRID, 1)])
def test_jobs_per_lane(n, nz, iterations):
    g = lu_builder(n, "B", iterations=iterations, nz=nz).build()
    assert [len(_lane(g, r)) for r in range(n)] \
        == _lane_len(n, nz, iterations)
    computes = sum(1 for j in g.jobs.values() if j.work > 0)
    assert computes == n * iterations * (2 * (nz - 2) + 1)


def test_class_b_64_ranks_job_counts():
    """Two SSOR iterations of class B on 64 ranks (8 x 8)."""
    g = lu_like(64, "B", iterations=2, nz=LU_GRID)
    lanes = [len(_lane(g, r)) for r in range(64)]
    assert len(g.jobs) == 90_627
    assert sum(1 for j in g.jobs.values() if j.work == 0) == 64_899
    assert max(lanes) == 1_618
    g.validate()


def test_plane_work_follows_the_block():
    """A plane's work is 0.125 x scale x ni.nj / 169 within the 10% skew;
    ``rhs`` is nz - 2 planes' worth."""
    g = lu_like(RANKS, "B", iterations=1, nz=NZ)
    xdim, ydim = lu_proc_grid(RANKS)
    for r in range(RANKS):
        ni = lu_extent(LU_GRID, xdim, r % xdim)
        nj = lu_extent(LU_GRID, ydim, r // xdim)
        plane = 0.125 * 4.0 * ni * nj / 169
        works = [g.jobs[jid] for jid in _lane(g, r) if g.jobs[jid].work > 0]
        assert len(works) == 2 * (NZ - 2) + 1
        for job in works[:-1]:
            assert 0.9 * plane <= job.work <= 1.1 * plane
            assert job.cpu_frac == 0.75
        rhs = works[-1]
        assert 0.9 * (NZ - 2) * plane <= rhs.work <= 1.1 * (NZ - 2) * plane
        assert rhs.cpu_frac == 0.60


def test_sweeps_depend_only_on_their_upwind_neighbours():
    """Each plane's compute job, with the markers before it on its lane,
    waits on its north and west neighbours in the lower sweep and on its
    south and east ones in the upper sweep, and on nobody else."""
    g = lu_like(RANKS, "B", iterations=1, nz=NZ)
    nbs = _neighbours(RANKS)
    for r in range(RANKS):
        lane = _lane(g, r)
        computes = [k for k, jid in enumerate(lane) if g.jobs[jid].work > 0]
        prev = -1
        for i, k in enumerate(computes):
            senders = set()
            for jid in lane[prev + 1:k + 1]:
                senders |= {d[0] for d in g.jobs[jid].deps if d[0] != r}
            prev = k
            if i < NZ - 2:
                want = {nbs[r][s] for s in "nw" if s in nbs[r]}
            elif i < 2 * (NZ - 2):
                want = {nbs[r][s] for s in "se" if s in nbs[r]}
            else:                      # rhs: the halo from every side
                want = set(nbs[r].values())
            assert senders == want, (r, i)


def test_a_send_releases_the_neighbours_compute():
    """Rank 0 starts the lower sweep with nothing to wait on; its south
    neighbour's first compute waits on rank 0's first plane."""
    g = lu_like(RANKS, "B", iterations=1, nz=NZ)
    first = _lane(g, 0)[0]
    assert g.jobs[first].work > 0 and not g.jobs[first].deps
    south = [jid for jid in _lane(g, 1)]
    k = next(k for k, jid in enumerate(south) if g.jobs[jid].work > 0)
    deps = {d for jid in south[:k + 1] for d in g.jobs[jid].deps
            if d[0] == 0}
    assert deps == {first}


# --------------------------------------------------- compiled vs event
def _grid(seed, bounds_frac=(0.3, 0.7)):
    g = lu_like(RANKS, "B", iterations=1, nz=NZ, seed=seed)
    specs = heterogeneous_cluster(RANKS, seed=seed)
    lo = min_feasible_cluster_bound(specs)
    hi = max_useful_cluster_bound(specs)
    return g, specs, [lo + f * (hi - lo) for f in bounds_frac]


@pytest.mark.parametrize("policy", ["equal-share", "oracle"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_compiled_sweep_matches_event_simulator(seed, policy):
    g, specs, bounds = _grid(seed)
    grid = [Scenario(name="lu", graph=g, specs=specs, bound_w=b,
                     policy=policy) for b in bounds]
    res = SweepEngine(executor="jax").run(grid)
    assert not res.failures
    for rec in res:
        assert rec.backend == "jax"
        ev = simulate(g, specs, rec.scenario.bound_w, policy=policy,
                      trace_every=None)
        assert rec.result.makespan == pytest.approx(ev.makespan, rel=1e-5)
        assert rec.result.energy_j == pytest.approx(ev.energy_j, rel=1e-5)


def _bucket_profile(graph, specs, bounds, policy):
    from repro.backends.jax import JaxBatchSimulator

    sim = JaxBatchSimulator(graph, specs, bounds, policy=policy)
    pending = sim.dispatch()
    sim.fetch(pending)
    return pending.profile


@pytest.mark.parametrize("policy", ["equal-share", "oracle"])
def test_settle_cascades_on_lu_not_on_is(policy):
    """The send/recv markers settle in about two rounds a wave; IS's
    collective waves in far fewer than one."""
    g, specs, bounds = _grid(0)
    lu = _bucket_profile(g, specs, bounds, policy)
    assert lu.settle_rounds >= lu.waves > 0
    assert lu.to_dict()["settle_rounds"] == lu.settle_rounds
    is_g = is_like(RANKS, "B", iterations=1, seed=0)
    is_ = _bucket_profile(is_g, specs, bounds, policy)
    assert is_.settle_rounds > 0
    assert lu.settle_rounds / lu.row_waves \
        > is_.settle_rounds / is_.row_waves
