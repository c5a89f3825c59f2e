"""Multi-device sharded-executor correctness (ISSUE 6).

This module wants a multi-device mesh: when it is imported before jax
initializes (the dedicated CI ``sharded`` job runs it first / alone) it
forces a 4-device host platform via ``XLA_FLAGS``; when jax was already
initialized single-device by an earlier module, the multi-device tests
skip and only the device-independent planner tests run.

Correctness bar: the sharded executor is **bit-identical** to the
single-device jax path (same compiled per-row stepper, rows merely
partitioned across devices), and both sit inside the differential
suite's envelopes against the event simulator (``2*dt`` makespan,
1% energy for exact policies).
"""

import os
import sys

import pytest

if "jax" not in sys.modules:  # must precede jax's backend init
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=4").strip()

jax = pytest.importorskip("jax")

from repro.core import (SweepEngine, homogeneous_cluster,  # noqa: E402
                        listing2_graph, listing2_uniform, scenario_grid,
                        simulate)
from repro.core.batchsim import estimate_row_bytes  # noqa: E402
from repro.core.sweep import plan_chunk_rows  # noqa: E402

DT = 0.05
MAKESPAN_ATOL = 2 * DT
ENERGY_RTOL = 0.01

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "before jax initializes")


def family_grid(policies=("equal-share", "oracle")):
    """A mixed-shape family: shared and padded buckets, plus a
    bound-schedule row, sized so 4 devices see uneven shards."""
    grid = scenario_grid(
        {"l2": listing2_graph(), "u10": listing2_uniform(10.0),
         "u7": listing2_uniform(7.0)},
        homogeneous_cluster(3), [2.5, 6.0, 9.0], policies)
    sched = scenario_grid({"l2s": listing2_graph()},
                          homogeneous_cluster(3), [9.0], policies,
                          bound_schedule=((15.0, 4.0),))
    return grid + sched


class TestPlanner:
    """Device-independent memory planning (no mesh required)."""

    def test_row_bytes_scales_with_envelope(self):
        small = estimate_row_bytes((4, 16, 4, 2, 4))
        big = estimate_row_bytes((8, 64, 8, 2, 4))
        assert 0 < small < big
        assert estimate_row_bytes((4, 16, 4, 2, 4), itemsize=8) \
            == 2 * small

    def test_row_bytes_covers_wide_clusters(self):
        """With more lanes than dependencies (N > D) the jax engine's
        (J+1, N) readiness table outgrows the (J+1, D) lists; the
        estimate counts the wider of the two, so it still over-estimates
        a real padded row."""
        import numpy as np

        from repro.backends.jax import JaxBatchSimulator
        from repro.core import layered_dag

        dims = (16, 63, 8, 4, 8)
        assert estimate_row_bytes(dims) == estimate_row_bytes(
            (16, 63, 8, 16, 8))
        assert estimate_row_bytes((16, 63, 8, 32, 8)) \
            > estimate_row_bytes(dims)
        g = layered_dag(12, layers=4, fan=2)
        sim = JaxBatchSimulator.padded([(g, homogeneous_cluster(12))],
                                       [20.0], pad_dims=dims)
        ctx = sim._ctx()
        geometry = sum(np.asarray(leaf).nbytes
                       for leaf in jax.tree_util.tree_leaves(ctx))
        assert sim.need.shape == (1, 64, 16)
        assert geometry < estimate_row_bytes(dims)

    def test_chunk_rows_aligned_and_floored(self):
        # budget of 10 rows, 4-way alignment -> 8 rows per chunk
        assert plan_chunk_rows(100, 1000, align=4) == 8
        assert plan_chunk_rows(100, 1000, align=1) == 10
        # a single shard-row over budget still dispatches one shard
        assert plan_chunk_rows(10_000, 1000, align=4) == 4
        assert plan_chunk_rows(10_000, 1000) == 1

    def test_zero_row_bytes_is_budget_bound(self):
        # a degenerate zero-byte row estimate must not divide by zero;
        # the cap degrades to the aligned row budget
        assert plan_chunk_rows(0, 1000, align=1) == 1000
        assert plan_chunk_rows(0, 1000, align=4) == 1000
        assert plan_chunk_rows(0, 1000, align=3) == 999

    def test_zero_budget_still_dispatches_one_shard(self):
        assert plan_chunk_rows(100, 0) == 1
        assert plan_chunk_rows(100, 0, align=4) == 4

    def test_align_wider_than_budget_wins(self):
        # 5 rows fit, but the shard width is 8: the documented minimum
        # is one full shard width even over budget
        assert plan_chunk_rows(100, 500, align=8) == 8

    def test_non_pow2_align(self):
        # nothing in the planner assumes power-of-two device counts
        assert plan_chunk_rows(100, 1000, align=3) == 9
        assert plan_chunk_rows(100, 1000, align=7) == 7
        assert plan_chunk_rows(100, 70, align=1) == 1

    def test_cap_never_exceeds_budget_except_one_shard_minimum(self):
        """Property sweep: the cap is always a positive multiple of the
        shard width, and it only exceeds the byte budget in the one
        documented case — the single-shard minimum dispatch."""
        import random

        rng = random.Random(7)
        for _ in range(500):
            row_bytes = rng.choice([0, 1, 7, 64, 1000, 10 ** 6])
            budget = rng.choice([0, 1, 999, 2 ** 10, 2 ** 20])
            align = rng.choice([1, 2, 3, 4, 7, 8, 16])
            cap = plan_chunk_rows(row_bytes, budget, align)
            assert cap >= align >= 1
            assert cap % align == 0
            if cap > align:  # above the minimum, the budget binds
                assert cap * row_bytes <= budget

    def test_budget_splits_buckets_without_changing_results(self):
        grid = family_grid()
        base = SweepEngine(executor="jax").run(grid)
        tiny = SweepEngine(executor="jax",
                           memory_budget_mb=0.001).run(grid)
        assert not base.failures and not tiny.failures
        assert len({r.bucket for r in tiny.records}) \
            > len({r.bucket for r in base.records})
        assert any(".1:" in (r.bucket or "") for r in tiny.records)
        for a, b in zip(tiny.records, base.records):
            assert a.result.makespan == pytest.approx(
                b.result.makespan, abs=1e-6)

    def test_pipeline_toggle_is_result_invariant(self):
        grid = family_grid()
        on = SweepEngine(executor="jax", pipeline=True).run(grid)
        off = SweepEngine(executor="jax", pipeline=False).run(grid)
        assert not on.failures and not off.failures
        for a, b in zip(on.records, off.records):
            assert a.result.makespan == pytest.approx(
                b.result.makespan, abs=1e-6)


class TestWaveCounts:
    """Waves per bucket from the rows' ``steps`` (no mesh required)."""

    def test_phantom_shard_rows_are_not_counted(self):
        import numpy as np

        from repro.backends.jax.engine import wave_counts

        # 5 rows on 4 shards of 2: [3, 5] [2, 7] [4, p] [p, p]; the
        # phantom rows replicate row 4 but could step any number
        steps = np.array([3, 5, 2, 7, 4, 99, 99, 99])
        assert wave_counts(steps, 5, 4) == (5 + 7 + 4, 21,
                                            2 * 5 + 2 * 7 + 1 * 4)
        assert wave_counts(steps[:5], 5, 1) == (7, 21, 5 * 7)

    def test_rows_that_divide_the_shards(self):
        import numpy as np

        from repro.backends.jax.engine import wave_counts

        steps = np.array([1, 4, 6, 2])
        assert wave_counts(steps, 4, 2) == (4 + 6, 13, 2 * 4 + 2 * 6)
        assert wave_counts(steps, 4, 4) == (13, 13, 13)


@multi_device
class TestShardedParity:
    def test_mesh_really_has_four_devices(self):
        from repro.backends.jax import shard_count

        assert len(jax.devices()) >= 4
        assert shard_count(None, 100) >= 4
        assert shard_count(None, 3) == 3      # clamped to rows
        assert shard_count(2, 100) == 2       # clamped to request
        assert shard_count(64, 100) == len(jax.devices())

    def test_sharded_matches_single_device_bitwise(self):
        """Same stepper, rows partitioned: no cross-device collective
        touches row math, so results are bit-identical."""
        grid = family_grid(("equal-share", "oracle", "heuristic", "ilp"))
        s4 = SweepEngine(executor="jax").run(grid)
        s1 = SweepEngine(executor="jax", shard_devices=1).run(grid)
        assert not s4.failures and not s1.failures
        assert {b.devices for b in s4.profile.buckets} >= {4}
        assert {b.devices for b in s1.profile.buckets} == {1}
        for a, b in zip(s4.records, s1.records):
            assert a.result.makespan == b.result.makespan
            assert a.result.energy_j == b.result.energy_j

    def test_sharded_within_event_envelopes(self):
        """The differential contract holds through the sharded path."""
        grid = family_grid(("equal-share", "oracle", "ilp"))
        sw = SweepEngine(executor="jax").run(grid)
        assert not sw.failures
        assert not sw.event_fallbacks()
        for r in sw.records:
            s = r.scenario
            ev = simulate(s.graph, list(s.specs), s.bound_w, s.policy,
                          latency_s=s.latency_s,
                          bound_schedule=s.bound_schedule)
            assert r.result.makespan == pytest.approx(
                ev.makespan, abs=MAKESPAN_ATOL), (s.name, s.policy)
            assert r.result.energy_j == pytest.approx(
                ev.energy_j, rel=ENERGY_RTOL), (s.name, s.policy)

    def test_row_padding_to_shard_multiple(self):
        """Row counts not divisible by the device count are padded with
        phantom rows on device and trimmed on fetch."""
        from repro.backends.jax import JaxBatchSimulator

        g = listing2_graph()
        specs = homogeneous_cluster(3)
        bounds = [2.5, 6.0, 7.5, 9.0, 12.0]       # 5 rows on 4 devices
        sharded = JaxBatchSimulator(g, specs, bounds).run()
        single = JaxBatchSimulator(g, specs, bounds,
                                   shard_devices=1).run()
        assert len(sharded) == len(bounds)
        for a, b in zip(sharded, single):
            assert a.makespan == b.makespan
            assert a.energy_j == b.energy_j

    def test_sharded_waves_leave_out_phantom_rows(self):
        """5 rows on 4 devices: the row-waves match one device's, and
        the phantom rows of the last shard add no waves."""
        from repro.backends.jax import JaxBatchSimulator

        g = listing2_graph()
        specs = homogeneous_cluster(3)
        bounds = [2.5, 6.0, 7.5, 9.0, 12.0]
        profs = []
        for devices in (4, 1):
            sim = JaxBatchSimulator(g, specs, bounds, policy="oracle",
                                    shard_devices=devices)
            pending = sim.dispatch()
            sim.fetch(pending)
            profs.append(pending.profile)
        sharded, single = profs
        assert sharded.devices == 4 and single.devices == 1
        assert sharded.row_waves == single.row_waves
        assert single.row_slots == 5 * single.waves
        assert sharded.row_waves <= sharded.row_slots
        # three shards hold real rows; their waves are at most 3x the
        # slowest row's, never 4x
        assert sharded.waves <= 3 * single.waves

    def test_sharded_stamps_match_single_device(self):
        """Every job's start and end time, through shared and padded
        buckets, is the same on four devices as on one."""
        grid = family_grid(("equal-share", "oracle"))
        s4 = SweepEngine(executor="jax").run(grid)
        s1 = SweepEngine(executor="jax", shard_devices=1).run(grid)
        assert not s4.failures and not s1.failures
        assert {b.devices for b in s4.profile.buckets} >= {4}
        for a, b in zip(s4.records, s1.records):
            assert a.result.job_starts == b.result.job_starts
            assert a.result.job_ends == b.result.job_ends

    def test_profile_reports_shard_and_phase_split(self):
        grid = family_grid()
        sw = SweepEngine(executor="jax").run(grid)
        prof = sw.profile
        assert prof is not None and prof.buckets
        for b in prof.buckets:
            assert b.devices >= 1 and b.rows >= 1
            assert b.cache_key is not None
            assert b.run_s >= 0 and b.transfer_s >= 0
        d = prof.to_dict()
        assert set(d) >= {"compiles", "cache_hits", "compile_s",
                          "run_s", "transfer_s", "buckets"}
        assert "jit:" in sw.backend_summary()
