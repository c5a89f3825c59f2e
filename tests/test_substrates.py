"""Substrate tests: optimizer (incl. int8 state), gradient compression,
data pipeline determinism, checkpoint atomicity + elastic restore."""

import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property-based tests skip without hypothesis
    from _hyp_stub import given, settings, st

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import (DataConfig, global_batch, host_batch,
                                 skewed_host_batch)
from repro.optim import (AdamWConfig, adamw_update, clip_by_global_norm,
                         compression, global_norm, init_opt_state,
                         lr_schedule)


# ---------------------------------------------------------------- optimizer
def quad_params():
    return {"w": jnp.asarray([1.5, -2.0, 0.5]),
            "b": jnp.asarray([[0.3, -0.7], [1.1, 0.0]])}


class TestAdamW:
    @pytest.mark.parametrize("state_dtype", ["float32", "bfloat16", "int8"])
    def test_converges_on_quadratic(self, state_dtype):
        cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                          total_steps=200, min_lr_frac=1.0,
                          state_dtype=state_dtype)
        params = quad_params()
        state = init_opt_state(params, cfg)
        for step in range(150):
            grads = jax.tree_util.tree_map(lambda p: 2 * p, params)  # d/dp p^2
            params, state, _ = adamw_update(params, grads, state,
                                            jnp.int32(step), cfg)
        norm = float(global_norm(params))
        assert norm < 0.05, f"{state_dtype}: |params|={norm}"

    def test_int8_tracks_fp32(self):
        """int8 moments stay close to the fp32 trajectory."""
        cfg32 = AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                            min_lr_frac=1.0, state_dtype="float32")
        cfg8 = AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=0,
                           min_lr_frac=1.0, state_dtype="int8")
        p32 = p8 = {"w": jnp.ones((8, 256)) * 2.0}
        s32 = init_opt_state(p32, cfg32)
        s8 = init_opt_state(p8, cfg8)
        key = jax.random.PRNGKey(0)
        for step in range(30):
            key, sub = jax.random.split(key)
            g = {"w": 2 * p32["w"] +
                 0.01 * jax.random.normal(sub, (8, 256))}
            p32, s32, _ = adamw_update(p32, g, s32, jnp.int32(step), cfg32)
            g8 = {"w": 2 * p8["w"] + 0.01 * jax.random.normal(sub, (8, 256))}
            p8, s8, _ = adamw_update(p8, g8, s8, jnp.int32(step), cfg8)
        diff = float(jnp.max(jnp.abs(p32["w"] - p8["w"])))
        assert diff < 0.1, f"int8 diverged from fp32 by {diff}"

    def test_grad_clipping(self):
        grads = {"w": jnp.full((4,), 100.0)}
        clipped, norm = clip_by_global_norm(grads, 1.0)
        assert float(norm) == pytest.approx(200.0)
        assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)

    def test_lr_schedule_shape(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_frac=0.1)
        assert float(lr_schedule(cfg, jnp.int32(0))) == pytest.approx(0.0)
        assert float(lr_schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
        end = float(lr_schedule(cfg, jnp.int32(100)))
        assert end == pytest.approx(0.1, rel=1e-3)

    @given(st.integers(0, 5))
    @settings(max_examples=5, deadline=None)
    def test_quantize_roundtrip_bounded(self, seed):
        from repro.optim.adamw import (_dequantize_blockwise,
                                       _quantize_blockwise)

        x = jax.random.normal(jax.random.PRNGKey(seed), (7, 130)) * 3.0
        codes, scale = _quantize_blockwise(x)
        back = _dequantize_blockwise(codes, scale, x.shape)
        err = jnp.max(jnp.abs(back - x))
        assert float(err) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


class TestCompression:
    def test_error_feedback_preserves_sum(self):
        """Over many steps, compressed grads sum to the true sum (EF)."""
        g_true = jax.random.normal(jax.random.PRNGKey(1), (64,))
        err = compression.init_error_feedback({"g": g_true})
        total_hat = jnp.zeros((64,))
        for _ in range(50):
            ghat, err_g = compression.compress_decompress(g_true, err["g"])
            err = {"g": err_g}
            total_hat = total_hat + ghat
        avg = total_hat / 50
        np.testing.assert_allclose(np.asarray(avg), np.asarray(g_true),
                                   atol=0.05)


# --------------------------------------------------------------------- data
class TestDataPipeline:
    CFG = DataConfig(vocab=1000, seq_len=64, global_batch=8, seed=3)

    def test_deterministic(self):
        a = host_batch(self.CFG, step=5, host=0, n_hosts=2)
        b = host_batch(self.CFG, step=5, host=0, n_hosts=2)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_hosts_disjoint_streams(self):
        a = host_batch(self.CFG, step=5, host=0, n_hosts=2)
        b = host_batch(self.CFG, step=5, host=1, n_hosts=2)
        assert not np.array_equal(a["tokens"], b["tokens"])

    def test_steps_differ(self):
        a = host_batch(self.CFG, step=1, host=0, n_hosts=2)
        b = host_batch(self.CFG, step=2, host=0, n_hosts=2)
        assert not np.array_equal(a["tokens"], b["tokens"])

    def test_global_assembly(self):
        g = global_batch(self.CFG, step=0, n_hosts=2)
        assert g["tokens"].shape == (8, 64)
        assert g["labels"].shape == (8, 64)
        # labels are next-token of tokens where not masked
        t, l = g["tokens"], g["labels"]
        inner = (t[:, 1:] == l[:, :-1]) | (l[:, :-1] == -1)
        assert inner.mean() > 0.95

    def test_skewed_host_has_more_work(self):
        a = host_batch(self.CFG, 0, 0, 2)
        s = skewed_host_batch(self.CFG, 0, 0, 2, skew_host=0)
        pad_a = (a["tokens"] == self.CFG.pad_id).sum()
        pad_s = (s["tokens"] == self.CFG.pad_id).sum()
        assert pad_s <= pad_a

    def test_encoder_family_frames(self):
        cfg = DataConfig(vocab=32, seq_len=16, global_batch=4,
                         family="encoder", d_model=24)
        b = host_batch(cfg, 0, 0, 1)
        assert b["frames"].shape == (4, 16, 24)
        assert b["labels"].shape == (4, 16)


# --------------------------------------------------------------- checkpoint
class TestCheckpoint:
    def make_tree(self, scale=1.0):
        return {"params": {"w": jnp.full((4, 8), scale),
                           "b": jnp.arange(3.0) * scale},
                "opt": {"m": jnp.zeros((4, 8))}}

    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        tree = self.make_tree(2.0)
        mgr.save(7, tree, extra={"loss": 1.25})
        restored, step, extra = mgr.restore(self.make_tree(0.0))
        assert step == 7 and extra["loss"] == 1.25
        np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                      np.asarray(tree["params"]["w"]))

    def test_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, self.make_tree(float(s)))
        assert mgr.completed_steps() == [3, 4]

    def test_crash_mid_write_ignored(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep_last=2)
        mgr.save(1, self.make_tree(1.0))
        # simulate a crashed writer: stray tmp dir with partial content
        crash = tmp_path / "step_000000002.tmp-deadbeef"
        crash.mkdir()
        (crash / "leaf_00000.npy").write_bytes(b"garbage")
        assert mgr.latest_step() == 1
        restored, step, _ = mgr.restore(self.make_tree(0.0))
        assert step == 1
        mgr.save(3, self.make_tree(3.0))  # gc cleans the crash dir
        assert not crash.exists()

    def test_structure_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, self.make_tree())
        bad = {"params": {"w": jnp.zeros((4, 8))}}  # missing leaves
        with pytest.raises(ValueError):
            mgr.restore(bad)

    def test_elastic_restore_with_shardings(self, tmp_path):
        """Restore re-places leaves with explicit shardings (1-device
        degenerate case of elastic re-shard onto a new mesh)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mgr = CheckpointManager(str(tmp_path))
        tree = self.make_tree(5.0)
        mgr.save(2, tree)
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), tree)
        restored, step, _ = mgr.restore(self.make_tree(0.0), shardings=sh)
        assert step == 2
        leaf = restored["params"]["w"]
        assert leaf.sharding == NamedSharding(mesh, P())
