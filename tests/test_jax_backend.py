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
