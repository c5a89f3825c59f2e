"""Unit tests for the compiled JAX execution backend (ISSUE 3).

Differential three-way coverage lives in ``test_batchsim_diff.py``;
this file covers the engine's own contract: validation, the jittable
policy registry, kernel-vs-reference engine parity, the heuristic's
approximate envelope, and the guarded-import surface that must stay
importable without jax installed.
"""

import pytest

from repro.backends import jax as jax_backend
from repro.core import (homogeneous_cluster, listing2_graph, simulate,
                        simulate_batch)

jax = pytest.importorskip("jax")

from repro.backends.jax import (JaxBatchSimulator,  # noqa: E402
                                simulate_batch_jax)
from repro.backends.jax.policy_fns import (get_jax_policy,  # noqa: E402
                                           has_jax_policy, jax_policies)


class TestGuardedSurface:
    def test_has_jax_reflects_environment(self):
        assert jax_backend.HAS_JAX is True
        assert jax_backend.jax_available() is True

    def test_unknown_attribute_raises(self):
        with pytest.raises(AttributeError):
            jax_backend.no_such_symbol  # noqa: B018


class TestPolicyRegistry:
    def test_all_vector_policies_have_jax_counterparts(self):
        from repro.policies import vector_policies

        assert set(vector_policies()) <= set(jax_policies())

    def test_exactness_contracts(self):
        for name in ("equal-share", "ilp", "ilp-makespan", "oracle"):
            assert get_jax_policy(name).exact, name
        heur = get_jax_policy("heuristic")
        assert not heur.exact and heur.wants_ticks

    def test_unknown_policy_raises(self):
        with pytest.raises(KeyError, match="no jax policy"):
            get_jax_policy("countdown")
        assert not has_jax_policy("countdown")


class TestValidation:
    def test_rejects_bad_dt(self):
        with pytest.raises(ValueError, match="dt"):
            simulate_batch_jax(listing2_graph(), homogeneous_cluster(3),
                               [6.0], dt=0.0)

    def test_rejects_empty_bounds(self):
        with pytest.raises(ValueError, match="bounds"):
            simulate_batch_jax(listing2_graph(), homogeneous_cluster(3),
                               [])

    def test_rejects_spec_mismatch(self):
        with pytest.raises(ValueError, match="NodeSpec"):
            simulate_batch_jax(listing2_graph(), homogeneous_cluster(2),
                               [6.0])

    def test_rejects_trace_retention(self):
        with pytest.raises(ValueError, match="trace"):
            simulate_batch_jax(listing2_graph(), homogeneous_cluster(3),
                               [6.0], trace_every=0.0)


class TestEngine:
    def test_matches_event_simulator_tightly(self):
        """Static caps + wave advancement at exact event times: float32
        noise only, far inside the differential envelope."""
        g = listing2_graph()
        specs = homogeneous_cluster(3)
        for bound in (2.5, 12.0):
            ev = simulate(g, specs, bound, "equal-share")
            jx = simulate_batch_jax(g, specs, [bound], "equal-share")[0]
            assert jx.makespan == pytest.approx(ev.makespan, rel=1e-5)
            assert jx.energy_j == pytest.approx(ev.energy_j, rel=1e-5)
            assert jx.job_ends.keys() == ev.job_ends.keys()

    def test_deadlock_detection(self):
        """An acyclic DAG whose deps cross against the lanes' serial
        execution order: each lane's first job waits on the other
        lane's *second* job, so nothing ever runs."""
        from repro.core import JobDependencyGraph

        g = JobDependencyGraph()
        g.add(0, 1, 5.0, deps=[(1, 2)])
        g.add(0, 2, 5.0)
        g.add(1, 1, 5.0, deps=[(0, 2)])
        g.add(1, 2, 5.0)
        with pytest.raises(RuntimeError, match="deadlock"):
            simulate_batch_jax(g, homogeneous_cluster(2), [6.0])
        # Tick policies keep a finite next-tick forever; the stall check
        # must still fire on the completion horizon, not spin max_steps.
        with pytest.raises(RuntimeError, match="deadlock"):
            simulate_batch_jax(g, homogeneous_cluster(2), [6.0],
                               "heuristic")

    def test_heuristic_tracks_vector_heuristic(self):
        """Same tick-quantized control plane as the numpy vector
        heuristic: the two approximate backends agree closely, and both
        stay within the event heuristic's documented 10% envelope."""
        g = listing2_graph()
        specs = homogeneous_cluster(3)
        for bound in (2.5, 6.0, 12.0):
            vec = simulate_batch(g, specs, [bound], "heuristic",
                                 dt=0.05)[0]
            jx = simulate_batch_jax(g, specs, [bound], "heuristic",
                                    dt=0.05)[0]
            ev = simulate(g, specs, bound, "heuristic")
            assert jx.makespan == pytest.approx(vec.makespan, rel=0.02)
            assert jx.makespan == pytest.approx(ev.makespan, rel=0.10)

    def test_heuristic_surges_above_bound(self):
        """The delayed cap application reproduces the vector
        heuristic's transient over-budget surges at tight bounds —
        same control plane, same surge accounting."""
        g = listing2_graph()
        specs = homogeneous_cluster(3)
        bound = 1.8
        vec = simulate_batch(g, specs, [bound], "heuristic", dt=0.05)[0]
        jx = simulate_batch_jax(g, specs, [bound], "heuristic",
                                dt=0.05)[0]
        assert jx.peak_power_w > bound
        assert jx.over_budget_time > 0
        assert jx.over_budget_time == pytest.approx(
            vec.over_budget_time, rel=0.05)

    def test_policy_instance_and_kwargs_routes(self):
        g = listing2_graph()
        specs = homogeneous_cluster(3)
        policy = get_jax_policy("equal-share")
        r = JaxBatchSimulator(g, specs, [6.0], policy=policy).run()[0]
        ref = simulate(g, specs, 6.0, "equal-share")
        assert r.makespan == pytest.approx(ref.makespan, rel=1e-5)
        with pytest.raises(ValueError, match="policy_kwargs"):
            JaxBatchSimulator(g, specs, [6.0], policy=policy,
                              time_limit=5.0)


class TestDispatchPipeline:
    def test_single_transfer_per_run(self, monkeypatch):
        """The whole output pytree comes back in ONE device-to-host
        fetch — eager per-field unpacking would sync once per array."""
        from repro.backends.jax import engine

        calls = []
        real = engine._device_get

        def counting(tree):
            calls.append(tree)
            return real(tree)

        monkeypatch.setattr(engine, "_device_get", counting)
        results = JaxBatchSimulator(listing2_graph(),
                                    homogeneous_cluster(3),
                                    [2.5, 6.0, 12.0]).run()
        assert len(results) == 3
        assert len(calls) == 1
        # ...and it really was the whole pytree, not a single leaf
        assert isinstance(calls[0], dict) and len(calls[0]) > 3

    def test_dispatch_fetch_round_trip(self):
        """run() == fetch(dispatch()) with a populated profile."""
        sim = JaxBatchSimulator(listing2_graph(), homogeneous_cluster(3),
                                [6.0, 9.0])
        pending = sim.dispatch()
        assert pending.profile.rows == 2
        assert pending.profile.cache_key is not None
        results = sim.fetch(pending)
        ref = simulate(listing2_graph(), homogeneous_cluster(3), 6.0,
                       "equal-share")
        assert results[0].makespan == pytest.approx(ref.makespan,
                                                    rel=1e-5)
        assert pending.profile.transfer_s >= 0.0

    def test_one_row_bucket_counts_its_waves(self):
        sim = JaxBatchSimulator(listing2_graph(), homogeneous_cluster(3),
                                [6.0])
        pending = sim.dispatch("l2:one")
        sim.fetch(pending)
        prof = pending.profile
        assert prof.bucket == "l2:one"
        assert prof.waves > 0
        assert prof.waves == prof.row_waves == prof.row_slots
        d = prof.to_dict()
        assert (d["waves"], d["row_waves"], d["row_slots"]) \
            == (prof.waves, prof.row_waves, prof.row_slots)

    def test_bucket_waves_are_its_rows_steps(self):
        """Each row steps as it would alone: the bucket's lockstep waves
        are its slowest row's, and its row-waves their sum."""
        g, specs = listing2_graph(), homogeneous_cluster(3)
        bounds = [2.5, 6.0, 12.0]
        alone = []
        for b in bounds:
            sim = JaxBatchSimulator(g, specs, [b], policy="oracle")
            pending = sim.dispatch()
            sim.fetch(pending)
            alone.append(pending.profile.waves)
        sim = JaxBatchSimulator(g, specs, bounds, policy="oracle")
        pending = sim.dispatch()
        sim.fetch(pending)
        prof = pending.profile
        assert prof.waves == max(alone)
        assert prof.row_waves == sum(alone)
        assert prof.row_slots == len(bounds) * prof.waves
        assert prof.row_waves <= prof.rows * prof.waves

    @pytest.mark.parametrize("pipeline", [True, False])
    def test_failed_fetch_keeps_profile(self, monkeypatch, pipeline):
        """Profiles are recorded at *dispatch*: a bucket whose fetch
        explodes must still appear in ``SweepResult.profile`` — under
        both the pipelined and the sequential dispatch paths (the
        sequential path used to drop it)."""
        from repro.core import SweepEngine, scenario_grid

        def exploding_fetch(self, pending):
            raise RuntimeError("transfer lost")

        monkeypatch.setattr(JaxBatchSimulator, "fetch", exploding_fetch)
        grid = scenario_grid({"l2": listing2_graph()},
                             homogeneous_cluster(3), [6.0, 9.0],
                             ["equal-share"])
        result = SweepEngine(executor="jax", pipeline=pipeline).run(grid)
        assert len(result.failures) == len(grid)
        assert all("transfer lost" in r.error for r in result.failures)
        assert result.profile is not None
        assert len(result.profile.buckets) == 1
        assert result.profile.buckets[0].bucket \
            == result.failures[0].bucket

    def test_compile_attribution_is_per_cache_key(self, monkeypatch):
        """Interleaved dispatches of a warm envelope and a fresh one:
        ``compiled`` lands on the fresh bucket only.  The old global
        cache-size delta charged whichever dispatch raced the check."""
        from repro.backends.jax import engine

        monkeypatch.setattr(engine, "_compiled_keys", set())
        g = listing2_graph()
        specs = homogeneous_cluster(3)
        warm = JaxBatchSimulator(g, specs, [6.0, 9.0])
        p1 = warm.dispatch()            # claims the envelope's key
        again = JaxBatchSimulator(g, specs, [2.5, 12.0])
        p2 = again.dispatch()           # same key -> cached
        fresh = JaxBatchSimulator(g, specs, [6.0, 9.0],
                                  policy="oracle")
        p3 = fresh.dispatch()           # new policy -> new key
        assert p1.profile.compiled is True
        assert p2.profile.compiled is False
        assert p3.profile.compiled is True
        assert p2.profile.cache_key == p1.profile.cache_key
        assert p3.profile.cache_key != p1.profile.cache_key
        assert p2.profile.compile_s == 0.0
        for sim, pending in ((warm, p1), (again, p2), (fresh, p3)):
            assert len(sim.fetch(pending)) == 2

    def test_claim_cache_key_single_winner_under_threads(self):
        """Concurrent dispatches of one envelope must attribute the
        compile to exactly one of them."""
        import threading

        from repro.backends.jax.engine import _claim_cache_key

        key = ("claim-race-test", 0)
        wins = []
        barrier = threading.Barrier(8)

        def claim():
            barrier.wait()
            if _claim_cache_key(key):
                wins.append(1)

        threads = [threading.Thread(target=claim) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(wins) == 1
        assert _claim_cache_key(key) is False

    def test_rerun_is_compile_free(self):
        """Re-running the same mixed family through the sweep engine
        must hit the jit cache on every bucket: the cache key (padding
        envelope + shard spec + policy name) is stable across runs."""
        from repro.core import (SweepEngine, listing2_uniform,
                                scenario_grid)

        grid = scenario_grid(
            {"l2": listing2_graph(), "u": listing2_uniform(10.0)},
            homogeneous_cluster(3), [6.0, 9.0],
            ["equal-share", "oracle"])
        engine = SweepEngine(executor="jax")
        first = engine.run(grid)
        assert not first.failures and first.profile is not None
        again = SweepEngine(executor="jax").run(grid)
        assert not again.failures
        assert again.profile.compiles == 0
        assert again.profile.cache_hits == len(again.profile.buckets)
        assert "jit:" in again.backend_summary()


class TestInterpretDefault:
    def test_cpu_defaults_to_interpreter(self):
        """power_step resolves interpret=None from the backend: the
        Pallas interpreter on CPU, native lowering elsewhere."""
        from repro.kernels.power_step import default_interpret

        expected = jax.default_backend() == "cpu"
        assert default_interpret() is expected

    def test_engine_inherits_backend_default(self):
        sim = JaxBatchSimulator(listing2_graph(), homogeneous_cluster(3),
                                [6.0], use_kernel=True)
        from repro.kernels.power_step import default_interpret

        assert sim.kernel_interpret == default_interpret()


class TestKernelEngineParity:
    def test_use_kernel_matches_ref_engine(self):
        """The Pallas-kernel engine (interpret mode) and the jnp
        reference engine walk identical wave sequences."""
        g = listing2_graph()
        specs = homogeneous_cluster(3)
        bounds = [2.5, 6.0, 12.0]
        for policy in ("equal-share", "oracle"):
            ref = simulate_batch_jax(g, specs, bounds, policy)
            ker = simulate_batch_jax(g, specs, bounds, policy,
                                     use_kernel=True,
                                     kernel_interpret=True)
            for a, b in zip(ref, ker):
                assert b.makespan == pytest.approx(a.makespan, rel=1e-6)
                assert b.energy_j == pytest.approx(a.energy_j, rel=1e-6)


def _two_lane_graph():
    """Same-lane and cross-lane single dependencies, and one job with
    two dependencies on one lane, listed later job first (its threshold
    is the later job's)."""
    from repro.core import JobDependencyGraph

    g = JobDependencyGraph()
    g.add(0, 0, 3.0)
    g.add(0, 1, 2.0, deps=[(0, 0)])              # same lane
    g.add(1, 0, 4.0, deps=[(0, 0)])              # cross lane
    g.add(1, 1, 1.0, deps=[(1, 0), (0, 1)])
    g.add(2, 0, 2.0)
    g.add(2, 1, 5.0, deps=[(1, 1), (0, 1), (0, 0)])
    g.add(0, 2, 2.0, deps=[(2, 1)])
    return g


def _readiness_batch(case):
    """A jax batch for each readiness case: an IS-like collective graph
    at 32 ranks, a graph of single dependencies, and a stacked batch
    padded past its rows (phantom lanes and phantom job slots)."""
    from repro.core.workloads import is_like, layered_dag

    if case == "is-collective":
        return JaxBatchSimulator(is_like(32, seed=3),
                                 homogeneous_cluster(32), [40.0, 90.0])
    if case == "single-deps":
        return JaxBatchSimulator(_two_lane_graph(), homogeneous_cluster(3),
                                 [6.0])
    items = [(is_like(16, seed=5), homogeneous_cluster(16)),
             (_two_lane_graph(), homogeneous_cluster(3)),
             (layered_dag(6, layers=5, fan=3), homogeneous_cluster(6))]
    return JaxBatchSimulator.padded(items, [30.0, 6.0, 12.0],
                                    pad_dims=(32, 512, 32, 32, 8))


def _plain_need(graph, n_lanes, n_slots):
    """``need`` for one row, job by job, from the graph itself."""
    import numpy as np

    job_ids = sorted(graph.jobs)
    lane = {nid: i for i, nid in enumerate(graph.nodes)}
    pos = {job.job_id: p for nid in graph.nodes
           for p, job in enumerate(graph.node_jobs(nid))}
    need = np.zeros((n_slots, n_lanes), np.int32)
    for k, jid in enumerate(job_ids):
        for dep in graph.jobs[jid].deps:
            m = lane[dep[0]]
            need[k, m] = max(need[k, m], pos[dep] + 1)
    return need


class TestReadiness:
    """The stepper decides readiness from lane progress (``ptr`` against
    the ``need`` table) instead of gathering every dependency from
    ``completed``; the two must agree on every reachable state."""

    @pytest.mark.parametrize("case", ["is-collective", "single-deps",
                                      "stacked-padded"])
    def test_mask_matches_completed_gather(self, case):
        import jax.numpy as jnp
        import numpy as np

        from repro.backends.jax import engine

        sim = _readiness_batch(case)
        ctx = jax.tree_util.tree_map(jnp.asarray, sim._ctx())
        rng = np.random.default_rng(14)
        n_states = 200
        rows = range(sim.n_rows) if sim.stacked else [None]
        ready_seen = blocked_seen = 0
        for r in rows:
            if r is None:
                ctx_r, deps_pad = ctx, sim.arrays.deps_pad
            else:      # one row of the stacked geometry (dt is shared)
                ctx_r = jax.tree_util.tree_map(
                    lambda x: x[r], ctx._replace(dt=None))._replace(
                        dt=ctx.dt)
                deps_pad = sim.arrays.deps_pad[r]
            node_seq = np.asarray(ctx_r.node_seq)
            j = len(ctx_r.work_pad) - 1
            n = node_seq.shape[0]
            lane_len = (node_seq < j).sum(axis=1)
            # states the stepper can reach: lane m has completed
            # exactly its first ptr[m] jobs
            ptr = np.floor(rng.random((n_states, n))
                           * (lane_len + 1)).astype(np.int32)
            completed = np.repeat(np.asarray(ctx_r.completed0)[None],
                                  n_states, axis=0)
            for s in range(n_states):
                for m in range(n):
                    completed[s, node_seq[m, :ptr[s, m]]] = True
            running = rng.random((n_states, n)) < 0.2
            cur = node_seq[np.arange(n)[None, :], ptr]
            want = (~running & (cur < j)
                    & completed[np.arange(n_states)[:, None, None],
                                deps_pad[cur]].all(axis=-1))

            def mask(p, run):
                st = engine._RowState(
                    ptr=p, running=run, remaining=jnp.ones(n),
                    row_t=0.0, bound=1.0, sched_idx=0,
                    done=jnp.zeros((), bool), stalled=False, energy=0.0,
                    peak=0.0, over_t=0.0, makespan=0.0, start_at=None,
                    end_at=None, tick_count=0, steps=0,
                    settle_rounds=0, waterfill_rounds=0)
                return engine._ready_mask(ctx_r, st)

            got = np.asarray(jax.vmap(mask)(ptr, running))
            np.testing.assert_array_equal(got, want)
            ready_seen += int(want.sum())
            blocked_seen += int((~want & ~running & (cur < j)).sum())
        # the states exercise both answers
        assert ready_seen > 0 and blocked_seen > 0

    @pytest.mark.parametrize("layout", ["shared", "stacked"])
    def test_need_table_matches_plain_build(self, layout):
        import numpy as np

        if layout == "shared":
            sim = _readiness_batch("is-collective")
            g = sim.graph
            assert sim.need.shape == (sim.n_jobs_total + 1, sim.n_nodes)
            np.testing.assert_array_equal(
                sim.need, _plain_need(g, sim.n_nodes, sim.n_jobs_total + 1))
            return
        sim = _readiness_batch("stacked-padded")
        n, j1 = sim.n_nodes, sim.n_jobs_total + 1
        assert sim.need.shape == (sim.n_rows, j1, n)
        assert sim.need.dtype == np.int32
        for r, g in enumerate(sim.row_graphs):
            want = _plain_need(g, n, j1)
            # real slots keep their ids; phantom slots and the sentinel
            # need nothing, and no job waits on a phantom lane
            np.testing.assert_array_equal(sim.need[r], want)
            assert not sim.need[r, :, int(sim.n_active[r]):].any()

    @pytest.mark.parametrize("policy", ["equal-share", "oracle"])
    def test_stamps_match_numpy_backend(self, policy):
        """Job start and end times on a collective graph agree with the
        numpy batch backend's (float32 against float64)."""
        from repro.core.workloads import is_like

        g, specs = is_like(8, seed=9), homogeneous_cluster(8)
        bounds = [12.0, 30.0]
        jx = simulate_batch_jax(g, specs, bounds, policy)
        vec = simulate_batch(g, specs, bounds, policy)
        for a, b in zip(jx, vec):
            assert a.job_starts.keys() == b.job_starts.keys() == g.jobs.keys()
            assert a.job_ends.keys() == b.job_ends.keys()
            for jid in g.jobs:
                assert a.job_starts[jid] == pytest.approx(
                    b.job_starts[jid], rel=1e-5, abs=1e-5), jid
                assert a.job_ends[jid] == pytest.approx(
                    b.job_ends[jid], rel=1e-5, abs=1e-5), jid


def _lu_batch(policy="equal-share", work=True):
    """A 16-rank NPB LU wavefront (send/recv markers on every lane), or
    the same graph with every job's work zeroed, as a warm-up runs it."""
    from repro.core import JobDependencyGraph
    from repro.core.workloads import lu_like

    g = lu_like(16, "B", iterations=1, nz=6, seed=4)
    if not work:
        g0 = JobDependencyGraph()
        for job in g.jobs.values():
            g0.add(job.node, job.index, 0.0, deps=list(job.deps),
                   cpu_frac=job.cpu_frac, tag=job.tag)
        g = g0
    return JaxBatchSimulator(g, homogeneous_cluster(16), [40.0, 90.0],
                             policy=policy)


class TestLaneState:
    """The settle loop carries lane state only: stamps are written per
    wave by lane position and laid out by job when the row ends, and a
    loop iteration unrolls several settle rounds."""

    @pytest.mark.parametrize("policy", ["equal-share", "oracle"])
    def test_lu_stamps_match_numpy_backend(self, policy):
        """Every job's start and end on a point-to-point wavefront, zero-
        work markers included, agree with the numpy batch backend's."""
        sim = _lu_batch(policy)
        g, specs = sim.graph, sim.specs
        vec = simulate_batch(g, specs, list(sim.bounds), policy)
        for a, b in zip(sim.run(), vec):
            assert a.job_starts.keys() == b.job_starts.keys() == g.jobs.keys()
            assert a.job_ends.keys() == b.job_ends.keys()
            for jid in g.jobs:
                assert a.job_starts[jid] == pytest.approx(
                    b.job_starts[jid], rel=1e-5, abs=1e-5), jid
                assert a.job_ends[jid] == pytest.approx(
                    b.job_ends[jid], rel=1e-5, abs=1e-5), jid

    @pytest.mark.parametrize("policy", ["equal-share", "oracle"])
    def test_unrolled_settle_changes_no_output(self, policy, monkeypatch):
        """Unrolling settle rounds is a schedule, not a result: every
        output, the settle rounds included, is bit for bit the one a
        round an iteration gives."""
        import functools

        import numpy as np

        from repro.backends.jax import engine

        args, statics = _lu_batch(policy)._pack()
        outs = []
        for unroll in (1, engine.SETTLE_UNROLL):
            monkeypatch.setattr(engine, "SETTLE_UNROLL", unroll)
            step = jax.jit(functools.partial(engine._vmapped_rows,
                                             **statics))
            out = jax.device_get(step(*args))
            outs.append({k: np.asarray(v) for k, v in out.items()})
        assert engine.SETTLE_UNROLL > 1
        for k, v in outs[0].items():
            if k in ("start_t", "end_t"):      # the sentinel slot is junk
                v, w = v[:, :-1], outs[1][k][:, :-1]
            else:
                w = outs[1][k]
            np.testing.assert_array_equal(v, w, err_msg=k)
        assert outs[0]["settle_rounds"].min() > outs[0]["steps"].max()

    @pytest.mark.parametrize("graph", ["lu", "is"])
    def test_zeroed_graph_compiles_the_real_graphs_stepper(self, graph):
        """A benchmark warms up on its graphs with the work zeroed (every
        job a zero-work one); the real graphs must then find the stepper
        compiled, whatever runs of zero-work jobs they hold."""
        from repro.backends.jax import engine
        from repro.core import JobDependencyGraph
        from repro.core.workloads import is_like

        if graph == "lu":
            warm, real = _lu_batch(work=False), _lu_batch()
        else:
            g = is_like(16, seed=7)
            g0 = JobDependencyGraph()
            for job in g.jobs.values():
                g0.add(job.node, job.index, 0.0, deps=list(job.deps),
                       cpu_frac=job.cpu_frac, tag=job.tag)
            warm, real = (JaxBatchSimulator(x, homogeneous_cluster(16),
                                            [40.0, 90.0]) for x in (g0, g))
        assert warm.arrays.work_pad.max() == 0 < real.arrays.work_pad.max()
        warm.run()
        compiled = engine.stepper_cache_size()
        pending = real.dispatch()
        real.fetch(pending)
        assert not pending.profile.compiled
        assert engine.stepper_cache_size() == compiled

    def test_shared_batches_of_one_graph_share_its_tables(self):
        """A sweep that runs a graph again (another pass, another policy)
        reuses the lane arrays and readiness table kept with the graph;
        adding a job rebuilds them."""
        a, b = _lu_batch(), _lu_batch("oracle")
        assert a.graph is not b.graph           # two builds of the graph
        c = JaxBatchSimulator(a.graph, a.specs, [60.0], policy="oracle")
        assert c.need is a.need and not c.need.flags.writeable
        assert c.arrays.node_seq is a.arrays.node_seq
        assert b.need is not a.need
        a.graph.add(99, 0, 1.0)
        d = JaxBatchSimulator(a.graph, list(a.specs) + a.specs[:1], [60.0])
        assert d.need.shape == (a.need.shape[0] + 1, a.need.shape[1] + 1)
        assert d.run()[0].makespan > 0

    def test_deadlock_names_only_the_jobs_that_never_ran(self):
        """``completed`` is laid out from the lanes' progress when the
        row ends: the job that ran before the stall is not reported."""
        from repro.core import JobDependencyGraph

        g = JobDependencyGraph()
        g.add(0, 0, 1.0)
        g.add(0, 1, 1.0, deps=[(1, 2)])
        g.add(0, 2, 1.0)
        g.add(1, 1, 1.0, deps=[(0, 2)])
        g.add(1, 2, 1.0)
        with pytest.raises(RuntimeError, match="deadlock") as err:
            simulate_batch_jax(g, homogeneous_cluster(2), [6.0])
        msg = str(err.value)
        assert "(0, 0)" not in msg
        for jid in ("(0, 1)", "(0, 2)", "(1, 1)", "(1, 2)"):
            assert jid in msg


def _is8_oracle(policy="oracle"):
    """IS at 8 ranks on the two stock tables, two bounds: a small NPB
    bucket whose oracle waves water-fill over two ``p_max`` levels."""
    from repro.core import heterogeneous_cluster, is_like
    from repro.core.power import (max_useful_cluster_bound,
                                  min_feasible_cluster_bound)

    g = is_like(8, "A", iterations=2, seed=3)
    specs = heterogeneous_cluster(8, seed=3)
    lo = min_feasible_cluster_bound(specs)
    hi = max_useful_cluster_bound(specs)
    return JaxBatchSimulator(g, specs, [lo + f * (hi - lo)
                                        for f in (0.25, 0.6)],
                             policy=policy)


def _stamp_digest(res):
    """First 16 hex digits of the SHA-256 of a result's job starts then
    ends (float64, jobs in sorted id order)."""
    import hashlib

    import numpy as np

    ids = sorted(res.job_starts)
    at = np.array([[res.job_starts[i] for i in ids],
                   [res.job_ends[i] for i in ids]], np.float64)
    return hashlib.sha256(at.tobytes()).hexdigest()[:16]


#: ``_is8_oracle()``'s results from the stepper whose water-fill unrolled
#: all N rounds on every wave (before the loop stopped at its fixed
#: point), on the CPU: per row the makespan and energy (``float.hex``)
#: and ``_stamp_digest``.  The while-loop must give the same bits.
IS8_ORACLE = [("0x1.66b2800000000p+5", "0x1.b6c0980000000p+9",
               "0fe4a48013aa6737"),
              ("0x1.3608200000000p+5", "0x1.4c4a600000000p+10",
               "2956da714718b5eb")]


class TestWaterfillRounds:
    @pytest.mark.parametrize("policy", ["equal-share", "oracle"])
    def test_bucket_counts_its_fill_rounds(self, policy, monkeypatch):
        """Oracle waves water-fill in one or two rounds on two tables
        (at most 3 a wave by the issue's bound); equal-share never
        fills.  The count is an arg of the ``results`` region."""
        from repro.obs import trace

        seen = []
        real = trace.region

        def recording(name, track, *a, **kw):
            seen.append((name, kw))
            return real(name, track, *a, **kw)

        monkeypatch.setattr(trace, "region", recording)
        sim = _is8_oracle(policy)
        pending = sim.dispatch()
        sim.fetch(pending)
        prof = pending.profile
        if policy == "oracle":
            assert 0 < prof.waterfill_rounds <= 3 * prof.row_waves
        else:
            assert prof.waterfill_rounds == 0
        assert prof.to_dict()["waterfill_rounds"] == prof.waterfill_rounds
        args, = [kw for name, kw in seen if name == "results"]
        assert args["waterfill_rounds"] == prof.waterfill_rounds

    def test_oracle_results_keep_the_unrolled_fills_bits(self):
        got = [(r.makespan.hex(), r.energy_j.hex(), _stamp_digest(r))
               for r in _is8_oracle().run()]
        assert got == IS8_ORACLE
