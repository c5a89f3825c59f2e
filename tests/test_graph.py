"""Graph layer tests — validated against the paper's own numbers:

* Fig. 4 walk-through: nominal total execution time = 19 time units,
  J_{*,2} all start at 3, the critical path starts at J_{2,1}, and the
  last jobs to finish are J_{2,5} and J_{3,5};
* Table I max-depths; Table II depth ranges.
"""

import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property-based tests skip without hypothesis
    from _hyp_stub import given, settings, st

from repro.core import (Job, JobDependencyGraph, listing2_graph,
                        listing2_random, listing2_uniform)
from repro.core.graph import GraphError

NOMINAL = lambda job: job.work  # noqa: E731  (work == nominal time)


@pytest.fixture(scope="module")
def g():
    return listing2_graph()


# ------------------------------------------------------------- paper Fig. 4
class TestListing2:
    def test_fifteen_jobs_three_nodes(self, g):
        assert len(g) == 15
        assert g.nodes == [1, 2, 3]

    def test_total_execution_time_is_19(self, g):
        assert g.makespan(NOMINAL) == pytest.approx(19.0)

    def test_j2_starts_at_3(self, g):
        start, _ = g.completion_times(NOMINAL)
        for i in (1, 2, 3):
            assert start[(i, 2)] == pytest.approx(3.0)

    def test_critical_path_starts_at_J21(self, g):
        path = g.critical_path(NOMINAL)
        assert path[0] == (2, 1)

    def test_last_jobs_are_J25_J35(self, g):
        _, comp = g.completion_times(NOMINAL)
        finishers = sorted(j for j, c in comp.items()
                           if c == pytest.approx(19.0))
        assert finishers == [(2, 5), (3, 5)]

    def test_table_I_max_depths(self, g):
        depth = g.max_depths()
        expected = {  # paper Table I
            (1, 1): 0, (2, 1): 0, (3, 1): 0,
            (1, 2): 1, (2, 2): 1, (3, 2): 1,
            (1, 3): 4, (2, 3): 2, (3, 3): 3,
            (1, 4): 5, (2, 4): 3, (3, 4): 4,
            (1, 5): 6, (2, 5): 6, (3, 5): 6,
        }
        assert depth == expected

    def test_table_II_depth_ranges(self, g):
        ranges = g.depth_ranges()
        expected = {  # paper Table II
            (1, 1): (0, 0), (2, 1): (0, 0), (3, 1): (0, 0),
            (1, 2): (1, 1), (2, 2): (1, 1), (3, 2): (1, 2),
            (1, 3): (4, 4), (2, 3): (2, 2), (3, 3): (3, 3),
            (1, 4): (5, 5), (2, 4): (3, 5), (3, 4): (4, 5),
            (1, 5): (6, 6), (2, 5): (6, 6), (3, 5): (6, 6),
        }
        assert ranges == expected

    def test_makespan_equals_longest_path_sum(self, g):
        """Definition 3: E_D = max over execution paths of the time sum."""
        best = max(sum(g[j].work for j in path)
                   for path in g.execution_paths())
        assert best == pytest.approx(g.makespan(NOMINAL))

    def test_roundtrip_text(self, g):
        g2 = JobDependencyGraph.from_text(g.to_text())
        assert set(g2.jobs) == set(g.jobs)
        assert g2.makespan(NOMINAL) == pytest.approx(19.0)
        for jid in g.jobs:
            assert set(g2[jid].deps) == set(g[jid].deps)


# ---------------------------------------------------------------- structure
class TestStructure:
    def test_initial_and_final_jobs(self, g):
        assert sorted(g.initial_jobs()) == [(1, 1), (2, 1), (3, 1)]
        assert sorted(g.final_jobs()) == [(1, 5), (2, 5), (3, 5)]

    def test_cycle_detection(self):
        g = JobDependencyGraph()
        g.add(0, 0, 1.0, deps=[(0, 1)])
        g.add(0, 1, 1.0, deps=[(0, 0)])
        with pytest.raises(GraphError):
            g.topological_order()

    def test_missing_dep_detection(self):
        g = JobDependencyGraph()
        g.add(0, 0, 1.0, deps=[(5, 5)])
        with pytest.raises(GraphError):
            g.topological_order()

    def test_duplicate_job_rejected(self):
        g = JobDependencyGraph()
        g.add(0, 0, 1.0)
        with pytest.raises(GraphError):
            g.add(0, 0, 2.0)

    def test_validate_multi_dep_same_node(self):
        g = JobDependencyGraph()
        g.add(1, 0, 1.0)
        g.add(1, 1, 1.0, deps=[(1, 0)])
        g.add(0, 0, 1.0)
        g.add(0, 1, 1.0, deps=[(0, 0), (1, 0), (1, 1)])
        with pytest.raises(GraphError, match="multiple jobs"):
            g.validate()

    def test_depth_level_sets_cover_every_job(self, g):
        levels = g.depth_level_sets()
        seen = {j for js in levels.values() for j in js}
        assert seen == set(g.jobs)
        # stretched job J_{3,2} appears at both levels 1 and 2 (§IV-A)
        assert (3, 2) in levels[1] and (3, 2) in levels[2]


# ------------------------------------------------------------ property tests
@st.composite
def random_dag(draw):
    """Layered random DAGs shaped like synchronised parallel programs."""
    n_nodes = draw(st.integers(2, 5))
    n_jobs = draw(st.integers(1, 6))
    g = JobDependencyGraph()
    for node in range(n_nodes):
        for j in range(n_jobs):
            deps = [(node, j - 1)] if j > 0 else []
            if j > 0 and draw(st.booleans()):
                other = draw(st.integers(0, n_nodes - 1))
                if other != node:
                    deps.append((other, j - 1))
            work = draw(st.floats(0.1, 50.0, allow_nan=False))
            g.add(node, j, work, deps=deps)
    return g


@given(random_dag())
@settings(max_examples=60, deadline=None)
def test_depth_range_invariants(g):
    """Delta(J) always starts at delta(J); children start strictly deeper;
    every parent's range ends before every child's max-depth."""
    depth = g.max_depths()
    ranges = g.depth_ranges()
    ch = g.children()
    for jid, (lo, hi) in ranges.items():
        assert lo == depth[jid]
        assert hi >= lo - 1
        for kid in ch[jid]:
            assert depth[kid] > hi  # stretching never crosses a child

    # makespan equals max completion, independent of enumeration
    mk = g.makespan(NOMINAL)
    _, comp = g.completion_times(NOMINAL)
    assert mk == pytest.approx(max(comp.values()))


@given(random_dag(), st.floats(1.1, 4.0))
@settings(max_examples=30, deadline=None)
def test_makespan_monotone_in_work(g, factor):
    """Scaling all work scales the makespan linearly (no hidden state)."""
    assert g.scaled(factor).makespan(NOMINAL) == \
        pytest.approx(factor * g.makespan(NOMINAL))


@given(st.floats(0.0, 6.0), st.integers(0, 5))
@settings(max_examples=20, deadline=None)
def test_listing2_random_valid(stddev, seed):
    g = listing2_random(stddev, seed=seed)
    assert len(g) == 15
    assert g.makespan(NOMINAL) > 0


def test_listing2_uniform_structure():
    g = listing2_uniform(10.0)
    assert g.makespan(NOMINAL) > 0
    assert g.max_depths() == listing2_graph().max_depths()


class TestDerived:
    """Values built from a graph alone are kept with it until a job is
    added, and travel without it."""

    def test_built_once_and_dropped_on_add(self):
        calls = []
        g = listing2_graph()

        def build(graph):
            calls.append(len(graph))
            return len(graph)

        assert g.derived("n", build) == g.derived("n", build) == len(g)
        assert calls == [len(g)]
        g.add(99, 0, 1.0)
        assert g.derived("n", build) == len(g)
        assert calls == [len(g) - 1, len(g)]

    def test_pickled_graph_leaves_them_behind(self):
        import pickle

        g = listing2_graph()
        g.derived("n", len)
        h = pickle.loads(pickle.dumps(g))
        assert h._derived == {} and g._derived == {"n": len(g)}
        assert h.to_text() == g.to_text()

    @pytest.mark.parametrize("case", ["listing2", "lu", "gaps"])
    def test_lane_arrays_match_a_plain_build(self, case):
        """The lanes are each node's jobs in index order, whatever the
        node ids; the arrays are kept and read-only."""
        import numpy as np

        from repro.core.batchsim import lane_arrays
        from repro.core.workloads import lu_like

        if case == "listing2":
            g = listing2_graph()
        elif case == "lu":
            g = lu_like(16, "B", iterations=1, nz=5)
        else:                      # sparse node ids, lanes of unequal length
            g = JobDependencyGraph()
            g.add(7, 3, 1.0)
            g.add(7, 1, 2.0, deps=[(2, 0)])
            g.add(2, 0, 3.0, cpu_frac=0.5)
        job_ids, work, rho, node_seq, deps = lane_arrays(g)
        assert lane_arrays(g)[3] is node_seq
        j = len(g)
        assert list(job_ids) == sorted(g.jobs)
        k_of = {jid: k for k, jid in enumerate(job_ids)}
        lanes = [[k_of[job.job_id] for job in g.node_jobs(nid)]
                 for nid in g.nodes]
        assert node_seq.shape == (len(lanes),
                                  max(len(s) for s in lanes) + 1)
        for row, lane in zip(node_seq, lanes):
            assert list(row[:len(lane)]) == lane
            assert (row[len(lane):] == j).all()
        for k, jid in enumerate(job_ids):
            assert work[k] == g[jid].work and rho[k] == g[jid].cpu_frac
            assert sorted(deps[k][deps[k] < j]) == sorted(
                k_of[d] for d in g[jid].deps)
        for a in (work, rho, node_seq, deps):
            assert not a.flags.writeable
            with pytest.raises(ValueError):
                a[0] = 0
        assert (work[j], rho[j]) == (0.0, 1.0)
