"""Pallas kernel tests: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,Hkv,S,dh", [
        (1, 4, 4, 128, 64),     # MHA
        (2, 8, 2, 256, 64),     # GQA 4:1
        (1, 4, 1, 128, 128),    # MQA, MXU-width head
        (1, 2, 2, 512, 32),     # long-ish seq
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, B, H, Hkv, S, dh, dtype, causal):
        k1, k2, k3 = jax.random.split(KEY, 3)
        q = rand(k1, (B, S, H, dh), dtype)
        k = rand(k2, (B, S, Hkv, dh), dtype)
        v = rand(k3, (B, S, Hkv, dh), dtype)
        got = ops.flash_attention(q, k, v, causal=causal, block_q=64,
                                  block_kv=64, interpret=True)
        want = ref.flash_attention_ref(
            jnp.swapaxes(jnp.swapaxes(q, 1, 2), 1, 2), k, v, causal=causal)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)

    def test_block_size_invariance(self):
        q = rand(KEY, (1, 256, 4, 64), jnp.float32)
        k = rand(KEY, (1, 256, 4, 64), jnp.float32)
        v = rand(KEY, (1, 256, 4, 64), jnp.float32)
        a = ops.flash_attention(q, k, v, block_q=64, block_kv=64,
                                interpret=True)
        b = ops.flash_attention(q, k, v, block_q=128, block_kv=32,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_rejects_ragged(self):
        q = rand(KEY, (1, 100, 4, 64), jnp.float32)
        with pytest.raises(ValueError):
            ops.flash_attention(q, q, q, block_q=64, block_kv=64,
                                interpret=True)


class TestRMSNorm:
    @pytest.mark.parametrize("shape", [(8, 128), (2, 16, 256), (1, 512),
                                       (3, 5, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, shape, dtype):
        x = rand(KEY, shape, dtype)
        gamma = rand(jax.random.PRNGKey(1), (shape[-1],), dtype) + 1.0
        got = ops.rmsnorm(x, gamma, interpret=True)
        want = ref.rmsnorm_ref(x, gamma)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)

    def test_matches_model_layer(self):
        from repro.models.layers import rmsnorm as model_rmsnorm

        x = rand(KEY, (4, 96), jnp.float32)
        g = rand(jax.random.PRNGKey(2), (96,), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(ops.rmsnorm(x, g, interpret=True)),
            np.asarray(model_rmsnorm(x, g)), rtol=1e-5, atol=1e-5)


class TestSSMScan:
    @pytest.mark.parametrize("B,H,S,P,N,chunk", [
        (1, 2, 64, 8, 16, 16),
        (2, 3, 128, 16, 8, 64),
        (1, 1, 256, 32, 32, 256),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, B, H, S, P, N, chunk, dtype):
        ks = jax.random.split(KEY, 5)
        x = rand(ks[0], (B, H, S, P), dtype)
        a = -jnp.abs(rand(ks[1], (B, H, S), jnp.float32)) * 0.2
        dt = jnp.abs(rand(ks[2], (B, H, S), jnp.float32))
        Bm = rand(ks[3], (B, S, N), dtype)
        Cm = rand(ks[4], (B, S, N), dtype)
        got = ops.ssm_scan(x, a, dt, Bm, Cm, chunk=chunk, interpret=True)
        want = ref.ssm_scan_ref(
            jnp.moveaxis(x, 1, 2).astype(jnp.float32),
            jnp.moveaxis(a, 1, 2), jnp.moveaxis(dt, 1, 2), Bm, Cm)
        want = jnp.moveaxis(want, 1, 2)  # back to (B,H,S,P)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)

    def test_chunk_invariance(self):
        ks = jax.random.split(KEY, 5)
        B, H, S, P, N = 1, 2, 128, 8, 8
        x = rand(ks[0], (B, H, S, P), jnp.float32)
        a = -jnp.abs(rand(ks[1], (B, H, S), jnp.float32)) * 0.2
        dt = jnp.abs(rand(ks[2], (B, H, S), jnp.float32))
        Bm = rand(ks[3], (B, S, N), jnp.float32)
        Cm = rand(ks[4], (B, S, N), jnp.float32)
        y1 = ops.ssm_scan(x, a, dt, Bm, Cm, chunk=32, interpret=True)
        y2 = ops.ssm_scan(x, a, dt, Bm, Cm, chunk=128, interpret=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)


def _unrolled_waterfill(tab, running, budget):
    """The water-fill as it was unrolled before its loop stopped at the
    fixed point: ``N`` copies of the round, whether live or not."""
    from repro.kernels.power_step import FIT_ATOL

    caps = jnp.broadcast_to(tab.cap_floor, running.shape)
    open_ = running
    rem = budget
    for _ in range(running.shape[-1]):
        n_open = jnp.sum(open_, axis=-1, keepdims=True)
        live = n_open > 0
        share = jnp.where(live, rem / jnp.maximum(n_open, 1), 0.0)
        sat = open_ & (tab.p_max <= share + FIT_ATOL)
        finished = live & ~jnp.any(sat, axis=-1, keepdims=True)
        caps = jnp.where(open_ & finished,
                         jnp.clip(share, tab.cap_floor, tab.p_max), caps)
        caps = jnp.where(sat, tab.p_max, caps)
        rem = rem - jnp.sum(jnp.where(sat, tab.p_max, 0.0), axis=-1,
                            keepdims=True)
        open_ = open_ & ~sat & ~finished
    return caps


#: Water-fill inputs: the first is the original mixed draw.
WATERFILL_CASES = ["mixed", "idle", "all-saturate", "below-floors",
                   "phantom", "two-pmax", "distinct-pmax"]


def _waterfill_case(case, rows=32):
    """``(table, tab, running (rows, N) bool, budget (rows,), two_tables)``
    for one water-fill case; ``two_tables`` marks the clusters of the
    two stock tables, whose caps must match the unrolled loop bitwise."""
    from repro.core.power import (heterogeneous_cluster, lut_table,
                                  stack_lut_tables)
    from repro.kernels.power_step import step_tables

    rng = np.random.default_rng(9)
    n = {"two-pmax": 64, "distinct-pmax": 48}.get(case, 5)
    table = lut_table(heterogeneous_cluster(n, seed=0))
    tab = step_tables(table)
    p_sum = float(table.p_max.sum())
    running = rng.random((rows, n)) < 0.6
    budget = rng.uniform(0.0, p_sum, rows)
    if case == "idle":
        running[:] = False
    elif case == "all-saturate":
        running[:] = True
        budget = rng.uniform(p_sum, 2.0 * p_sum, rows)
    elif case == "below-floors":
        budget = rng.uniform(0.0, float(table.cap_floor.min()), rows)
    elif case == "phantom":
        stacked = stack_lut_tables([table], n + 3, table.state_p.shape[1])
        tab = type(tab)(*(a[0] for a in step_tables(stacked)))
        running = np.concatenate([running, np.zeros((rows, 3), bool)], 1)
    elif case == "distinct-pmax":
        # every lane its own saturation level, floors kept below
        p_max = np.asarray(tab.p_max) * rng.uniform(1.0, 1.5, (1, n))
        tab = tab._replace(p_max=p_max.astype(np.float32))
        budget = rng.uniform(0.0, float(p_max.sum()), rows)
    return (table, tab, running, budget.astype(np.float32),
            case != "distinct-pmax")


class TestPowerStep:
    """Fused power-redistribution step: Pallas (interpret) vs jnp
    reference, and both vs the numpy translation/waterfill oracles."""

    def _tables(self, n=5, seed=0):
        from repro.core.power import heterogeneous_cluster, lut_table
        from repro.kernels.power_step import step_tables

        specs = heterogeneous_cluster(n, seed=seed)  # ragged LUT pads
        table = lut_table(specs)
        return specs, table, step_tables(table)

    def _inputs(self, table, seed=1):
        n = table.n_nodes
        rng = np.random.default_rng(seed)
        caps = rng.uniform(0.2, 1.2 * float(table.p_max.max()), (1, n))
        running = (rng.random((1, n)) < 0.7).astype(np.float32)
        remaining = rng.uniform(0.0, 50.0, (1, n))
        rho = rng.uniform(0.1, 1.0, (1, n))
        bound = np.array([[rng.uniform(float(table.idle_w.sum()),
                                       float(table.p_max.sum()))]])
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        return tuple(map(f32, (caps, running, remaining, rho, bound)))

    @pytest.mark.parametrize("redistribute", [False, True])
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_pallas_matches_ref(self, redistribute, seed):
        from repro.kernels.power_step import (power_step_pallas,
                                              power_step_ref)

        _, table, tab = self._tables()
        args = self._inputs(table, seed=seed)
        got = power_step_pallas(tab, *args, redistribute=redistribute,
                                interpret=True)
        want = power_step_ref(tab, *args, redistribute=redistribute)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-6)

    def test_pallas_matches_ref_under_vmap(self):
        """The engine vmaps the kernel over the bound axis; Pallas'
        batching rule must agree with vmapping the reference."""
        from repro.kernels.power_step import (power_step_pallas,
                                              power_step_ref)

        _, table, tab = self._tables()
        rows = [self._inputs(table, seed=s) for s in (4, 5, 6)]
        batched = tuple(jnp.stack(a) for a in zip(*rows))
        got = jax.vmap(lambda c, r, m, h, b: power_step_pallas(
            tab, c, r, m, h, b, redistribute=True, interpret=True))(*batched)
        want = jax.vmap(lambda c, r, m, h, b: power_step_ref(
            tab, c, r, m, h, b, redistribute=True))(*batched)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-6)

    def test_translate_matches_numpy_oracle(self):
        """The in-kernel gather reproduces batched_operating_point /
        batched_rates (the numpy backend's translator) on a mixed grid
        of caps, including sub-p_min duty states and ragged LUT pads."""
        from repro.core.power import (batched_operating_point,
                                      batched_rates)
        from repro.kernels.power_step import power_step_ref

        _, table, tab = self._tables()
        n = table.n_nodes
        rng = np.random.default_rng(7)
        caps = rng.uniform(0.2, 1.2 * float(table.p_max.max()), (16, n))
        freq, duty, power = batched_operating_point(table, caps)
        rho = rng.uniform(0.1, 1.0, (16, n))
        rate_np = batched_rates(table, freq, duty, rho)
        remaining = rng.uniform(0.1, 50.0, (16, n))
        for i in range(16):
            f32 = lambda a: jnp.asarray(a[i:i + 1], jnp.float32)  # noqa: E731
            rate, p_node, t_fin, eff, p_cl, t_comp, _ = power_step_ref(
                tab, f32(caps), jnp.ones((1, n), jnp.float32),
                f32(remaining), f32(rho), jnp.ones((1, 1), jnp.float32))
            np.testing.assert_allclose(np.asarray(rate)[0], rate_np[i],
                                       rtol=1e-5)
            np.testing.assert_allclose(np.asarray(p_node)[0], power[i],
                                       rtol=1e-5)
            np.testing.assert_allclose(np.asarray(t_comp)[0, 0],
                                       (remaining[i] / rate_np[i]).min(),
                                       rtol=1e-4)

    @pytest.mark.parametrize("case", WATERFILL_CASES)
    def test_waterfill_matches_numpy_oracle(self, case):
        """waterfill_caps under ``jit(vmap(...))``, as the engine runs
        it, gives the caps of the N-fold unrolled loop it replaced, in
        at most as many rounds as the running lanes have distinct
        ``p_max`` values; the mixed case also agrees with the vector
        backend's batched_waterfill row for row."""
        from repro.kernels.power_step import waterfill_caps
        from repro.policies.vector import batched_waterfill

        table, tab, running, budget, two_tables = _waterfill_case(case)
        args = (tab, jnp.asarray(running[:, None, :]),
                jnp.asarray(budget[:, None, None], jnp.float32))
        got, rounds = jax.jit(jax.vmap(waterfill_caps,
                                       in_axes=(None, 0, 0)))(*args)
        want = jax.jit(jax.vmap(_unrolled_waterfill,
                                in_axes=(None, 0, 0)))(*args)
        got, want = np.asarray(got)[:, 0], np.asarray(want)[:, 0]
        if two_tables:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_array_max_ulp(got, want, maxulp=2)
        p_max = np.asarray(tab.p_max)[0]
        levels = np.array([len(np.unique(p_max[r])) for r in running])
        rounds = np.asarray(rounds)
        assert (rounds <= levels).all()
        assert ((rounds > 0) == running.any(axis=1)).all()
        if case == "mixed":
            np.testing.assert_allclose(
                got, batched_waterfill(running, budget, table),
                rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("bound_frac", [0.3, 0.7, 1.2])
    def test_pallas_matches_ref_through_fill_rounds(self, bound_frac):
        """Oracle waves whose water-fill takes one or two rounds (two
        node tables): the interpreted kernel and the reference agree,
        rounds included."""
        from repro.kernels.power_step import (power_step_pallas,
                                              power_step_ref)

        _, table, tab = self._tables(n=12, seed=4)
        rows = [self._inputs(table, seed=s) for s in range(10, 16)]
        caps, running, remaining, rho, _ = (jnp.stack(a)
                                            for a in zip(*rows))
        bound = jnp.full((len(rows), 1, 1),
                         bound_frac * float(table.p_max.sum()), jnp.float32)
        got = jax.vmap(lambda c, r, m, h, b: power_step_pallas(
            tab, c, r, m, h, b, redistribute=True, interpret=True))(
                caps, running, remaining, rho, bound)
        want = jax.vmap(lambda c, r, m, h, b: power_step_ref(
            tab, c, r, m, h, b, redistribute=True))(
                caps, running, remaining, rho, bound)
        for g, w in zip(got[:-1], want[:-1]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got[-1]),
                                      np.asarray(want[-1]))
        assert np.asarray(want[-1]).max() >= 1

