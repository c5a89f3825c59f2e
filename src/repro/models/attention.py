"""Grouped-query attention: training/prefill and cached-decode paths.

The XLA einsum path below is the default (and the one the multi-pod
dry-run lowers); ``repro.kernels.flash_attention`` provides the Pallas TPU
kernel with identical math, selected via ``impl='pallas'`` where supported.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import Params, apply_rope, dense_init


def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int,
              head_dim: int, dtype, qkv_bias: bool = False) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d_model, n_heads * head_dim, dtype),
        "wk": dense_init(kk, d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(kv, d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ko, n_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


def _project_qkv(params: Params, x: jnp.ndarray, n_heads: int,
                 n_kv_heads: int, head_dim: int):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    return q, k, v


def gqa_scores_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool,
                    window: int = 0) -> jnp.ndarray:
    """(…, Sq, Sk) boolean keep-mask from positions."""
    rel = q_pos[..., :, None] - k_pos[..., None, :]
    keep = jnp.ones(rel.shape, bool)
    if causal:
        keep &= rel >= 0
    if window > 0:
        keep &= rel < window
    return keep


def gqa_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               keep: Optional[jnp.ndarray],
               decode_layout: bool = False) -> jnp.ndarray:
    """q: (B,Sq,H,dh); k/v: (B,Sk,Hkv,dh); GQA by head grouping.

    fp32 softmax accumulation; returns (B,Sq,H,dh) in q.dtype.
    Materialises (Sq, Sk) scores — use only for short Sq (decode) or tiny
    smoke shapes; long sequences go through :func:`blocked_attend`.

    ``decode_layout`` pins the scores to batch-only sharding so a
    dh-sharded KV cache contracts locally (partial sums + a small
    all-reduce — the flash-decoding split), instead of GSPMD gathering
    the whole cache (§Perf iteration A1).
    """
    from .sharding import constrain

    B, Sq, H, dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    q = q.reshape(B, Sq, Hkv, group, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32)
    if decode_layout:
        scores = constrain(scores, "dp", None, None, None, None)
    scores = scores / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    if keep is not None:
        scores = jnp.where(keep[:, None, None, :, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
    if decode_layout:
        out = constrain(out, "dp", None, None, None, None)
    return out.reshape(B, Sq, H, dh)


def blocked_attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool,
                   window: int = 0, block_q: int = 1024,
                   block_kv: int = 1024) -> jnp.ndarray:
    """Flash-style blocked attention on the XLA path (online softmax over
    KV chunks, lax.map over Q chunks) — O(S * block) memory instead of
    O(S^2).  This is the same math as kernels/flash_attention.py; the
    Pallas kernel is the TPU-tiled version of this loop.

    q (B,S,H,dh); k/v (B,S,Hkv,dh); q_pos/k_pos (S,) position vectors.
    """
    B, S, H, dh = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    bq = min(block_q, S)
    bk = min(block_kv, S)
    nq = (S + bq - 1) // bq
    nk = (S + bk - 1) // bk
    assert S % bq == 0 and S % bk == 0, "seq must divide block sizes"

    # inputs stay in model dtype (bf16): only scores/normalisers/acc are
    # fp32 — halves the live QKV footprint for long sequences
    qf = q.reshape(B, nq, bq, Hkv, g, dh)
    kf = k.reshape(B, nk, bk, Hkv, dh)
    vf = v.reshape(B, nk, bk, Hkv, dh)
    qp = q_pos.reshape(nq, bq)
    kp = k_pos.reshape(nk, bk)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))

    def q_block(args):
        qb, qpb = args  # (B,bq,Hkv,g,dh), (bq,)

        def kv_step(carry, xs):
            m, l, acc = carry
            kb, vb, kpb = xs  # (B,bk,Hkv,dh), (B,bk,Hkv,dh), (bk,)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            rel = qpb[:, None] - kpb[None, :]
            keep = jnp.ones(rel.shape, bool)
            if causal:
                keep &= rel >= 0
            if window > 0:
                keep &= rel < window
            s = jnp.where(keep[None, :, None, None, :], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + \
                jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(vb.dtype), vb,
                           preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, bq, Hkv, g), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, bq, Hkv, g), jnp.float32)
        a0 = jnp.zeros((B, bq, Hkv, g, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step, prevent_cse=False), (m0, l0, a0),
            (jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0), kp))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(q_block, (jnp.moveaxis(qf, 1, 0), qp))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, H, dh)
    return out.astype(q.dtype)


#: sequences at or above this length use the blocked (flash-style) path
BLOCKED_ATTN_THRESHOLD = 2048


def attention(params: Params, x: jnp.ndarray, positions: jnp.ndarray,
              *, n_heads: int, n_kv_heads: int, head_dim: int,
              causal: bool = True, window: int = 0,
              rope_theta: float = 500000.0,
              use_rope: bool = True) -> jnp.ndarray:
    """Full-sequence attention (training / prefill)."""
    B, S, d = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    if S >= BLOCKED_ATTN_THRESHOLD:
        from .sharding import constrain

        # Hoist the sequence gather of K/V out of the blocked-attention
        # loops: with the residual stream sequence-sharded over `model`,
        # leaving the gather implicit put an all-gather *inside* the
        # q-block loop — XLA does not hoist loop-invariant collectives —
        # costing n_q x n_kv redundant gathers (573 GiB/dev/step observed
        # on llama3-8b prefill_32k).  Gather once per layer; queries stay
        # sequence-sharded so each device attends its q-shard against the
        # full K/V (§Perf carry-over fix).
        k = constrain(k, "dp", None, None, None)
        v = constrain(v, "dp", None, None, None)
        q = constrain(q, "dp", "mdl", None, None)
        pos1d = positions[0] if positions.ndim == 2 else positions
        out = blocked_attend(q, k, v, pos1d, pos1d, causal, window)
    else:
        keep = None
        if causal or window:
            keep = gqa_scores_mask(positions, positions, causal, window)
        out = gqa_attend(q, k, v, keep)
    return out.reshape(B, S, n_heads * head_dim) @ params["wo"]


def decode_attend_seqsharded(q: jnp.ndarray, k_cache: jnp.ndarray,
                             v_cache: jnp.ndarray, new_k: jnp.ndarray,
                             new_v: jnp.ndarray, pos: jnp.ndarray,
                             window: int = 0):
    """Flash-decoding via shard_map: KV cache sharded along S over the
    model axis; the cache write lands only on the owning shard (local
    dynamic_update_slice) and the softmax combines per-shard partials
    with tiny psum/pmax collectives (§Perf iteration A2).

    Under plain GSPMD a dynamic-position write into a sequence-sharded
    cache triggers "involuntary full rematerialization" — the whole cache
    is gathered, converted and re-sharded every step (observed: 22.8
    GiB/dev for qwen decode_32k).  shard_map makes the ownership explicit.

    q (B,1,H,dh); caches (B,S,Hkv,dh); new_k/new_v (B,1,Hkv,dh);
    pos scalar.  Requires an active sharding policy; returns
    (out (B,1,H,dh), k_cache, v_cache).
    """
    from jax.sharding import PartitionSpec as P

    from .sharding import _LOCAL

    mesh, dp, mdl = _LOCAL.policy
    B, S, Hkv, dh = k_cache.shape
    H = q.shape[2]
    g = H // Hkv
    n_seq = mesh.shape[mdl]
    S_loc = S // n_seq
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    dp_ax = dp if isinstance(dp, str) else dp[-1]
    b_spec = dp if B % _policy_axis_size(mesh, dp) == 0 else None

    def local_fn(q_l, kc, vc, nk, nv, pos_l):
        # kc/vc: (B_loc, S_loc, Hkv, dh) — this shard's positions
        idx = jax.lax.axis_index(mdl)
        start = idx * S_loc
        off = pos_l - start
        in_range = (off >= 0) & (off < S_loc)
        off_c = jnp.clip(off, 0, S_loc - 1)
        Bl = kc.shape[0]
        row_k = jax.lax.dynamic_slice(kc, (0, off_c, 0, 0),
                                      (Bl, 1, Hkv, dh))
        row_v = jax.lax.dynamic_slice(vc, (0, off_c, 0, 0),
                                      (Bl, 1, Hkv, dh))
        kc = jax.lax.dynamic_update_slice(
            kc, jnp.where(in_range, nk.astype(kc.dtype), row_k),
            (0, off_c, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, jnp.where(in_range, nv.astype(vc.dtype), row_v),
            (0, off_c, 0, 0))

        qf = q_l.reshape(Bl, 1, Hkv, g, dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kc,
                       preferred_element_type=jnp.float32) * scale
        kpos = start + jnp.arange(S_loc)
        keep = kpos <= pos_l
        if window > 0:
            keep &= kpos > (pos_l - window)
        s = jnp.where(keep[None, None, None, None, :], s, -1e30)
        m_loc = jnp.max(s, axis=-1)                      # (B,Hkv,g,1)
        m = jax.lax.pmax(m_loc, mdl)
        p = jnp.exp(s - m[..., None])
        l_loc = jnp.sum(p, axis=-1)
        acc_loc = jnp.einsum("bhgqk,bkhd->bhgqd",
                             p.astype(vc.dtype), vc,
                             preferred_element_type=jnp.float32)
        l = jax.lax.psum(l_loc, mdl)
        acc = jax.lax.psum(acc_loc, mdl)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = jnp.moveaxis(out, 3, 1).reshape(Bl, 1, H, dh)
        return out.astype(q_l.dtype), kc, vc

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(b_spec, None, None, None),
                  P(b_spec, mdl, None, None), P(b_spec, mdl, None, None),
                  P(b_spec, None, None, None), P(b_spec, None, None, None),
                  P()),
        out_specs=(P(b_spec, None, None, None),
                   P(b_spec, mdl, None, None), P(b_spec, mdl, None, None)),
        check_vma=False)
    return fn(q, k_cache, v_cache, new_k, new_v, pos)


def _policy_axis_size(mesh, axes) -> int:
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _seqsharded_available(S: int) -> bool:
    from .sharding import _LOCAL

    policy = getattr(_LOCAL, "policy", None)
    if policy is None:
        return False
    mesh, _dp, mdl = policy
    return mdl in mesh.axis_names and S % mesh.shape[mdl] == 0


def attention_decode(params: Params, x: jnp.ndarray, pos: jnp.ndarray,
                     k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     *, n_heads: int, n_kv_heads: int, head_dim: int,
                     window: int = 0, rope_theta: float = 500000.0,
                     use_rope: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode against a KV cache (aligned batch).

    x: (B, 1, d); pos: scalar int32 (all lanes decode the same step, the
    serving engine's continuous-batching layer keeps lanes aligned);
    caches (B, S_max, Hkv, dh).  The cache write is a one-slot
    dynamic_update_slice — O(Hkv*dh) bytes, not O(S_max) — so decode stays
    memory-roofline-faithful.  Returns (out (B,1,d), new_k, new_v).
    """
    B, _one, d = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    if use_rope:
        posv = jnp.full((B, 1), pos, jnp.int32)
        q = apply_rope(q, posv, rope_theta)
        k = apply_rope(k, posv, rope_theta)
    S_max = k_cache.shape[1]
    if _seqsharded_available(S_max):
        out, k_cache, v_cache = decode_attend_seqsharded(
            q, k_cache, v_cache, k, v, pos, window=window)
    else:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
        kpos = jnp.arange(S_max)
        keep = kpos <= pos
        if window > 0:
            keep &= kpos > (pos - window)
        keep = jnp.broadcast_to(keep[None, None, :], (B, 1, S_max))
        out = gqa_attend(q, k_cache, v_cache, keep, decode_layout=True)
    out = out.reshape(B, 1, n_heads * head_dim) @ params["wo"]
    return out, k_cache, v_cache
