"""Compiled wave-advancement engine (``lax.while_loop`` + ``vmap``).

This is :class:`~repro.core.batchsim.BatchSimulator`'s state machine —
wave time advancement with completions, dependency hand-offs, energy
accounting and policy caps resolved at exact event times — ported to a
compiled ``jax.lax.while_loop`` stepper.  The stepper is written for a
*single* scenario row (``(N,)`` lane state, ``(N, K)`` job stamps)
and ``jax.vmap``-ed over the row axis.  Two batch layouts share it:

* **shared** (the constructor): one graph and cluster, B bounds — the
  static geometry (:class:`_Ctx`) broadcasts (``in_axes=None``) and
  only the bound axis is mapped;
* **stacked** (:meth:`JaxBatchSimulator.padded`): B different (graph,
  cluster) rows padded to one envelope — the geometry itself carries a
  leading row axis and is mapped with the bounds.  Padding is masked
  exactly as in the numpy backend (phantom job slots born completed,
  phantom lanes with zero idle draw; see
  :class:`repro.core.batchsim.BatchArrays`).

Dependencies reach the device as lane-progress thresholds, not as
lists: ``ctx.need[k, m]`` (built once per batch on the host by
:func:`readiness_table`) is how many jobs lane ``m`` must have
completed before job slot ``k`` may start.  A lane completes its jobs
in ``node_seq`` order and ``ptr`` counts them, so readiness is one row
gather, one compare and one reduction, ``ptr >= need[cur]``, with no
per-dependency gather into the ``completed`` flags.  The same holds
for the job stamps: every start and completion of a wave happens at the
row's one instant, so the loop carries only lane state, and each wave
ends by stamping the ``(N, K)`` lane positions its starts and
completions passed (one masked write); the ``(J+1,)`` stamps and
``completed`` flags are scattered from them once, when the row ends.
``_settle``'s rounds therefore touch nothing ``J``-sized, and a loop
iteration unrolls :data:`SETTLE_UNROLL` of them: a wave whose cascade
runs through send/recv markers (2 to 4 rounds) settles in one
iteration, and a round past the fixed point changes nothing.

Per wave, the hot path — LUT power->frequency gather, per-node rate
computation, earliest-event reduction, and (for redistribution policies)
idle-power reclamation/water-fill — is one call into
:func:`repro.kernels.power_step.power_step`, plain ``jnp`` on the row's
``(N,)`` lanes and scalars.  The row's *current* cluster bound is a
traced operand of that call, so dynamic bound schedules flow straight
through its reclamation/water-fill step: each row carries its padded
``(T,)`` change-time/watt arrays, the wave advancement stops at the
next arrival exactly like it stops at completions and policy ticks, and
the updated bound feeds the very next wave's caps.

Numerics: the engine runs in JAX's default float32.  Job completion is
decided by *time* comparison (``t_fin <= delta``), never by a residual
remaining-work epsilon, so float32 cannot livelock a lane; the
differential suite holds the results to the same ``2*dt`` makespan / 1%
energy envelopes as the numpy backend.

The jitted steppers are module-level functions keyed only on array
shapes and static policy/shard config, so same-shape batches — every
bucket of a sweep grid — share one compilation; the sweep engine's
power-of-two padding envelopes make repeated mixed-family sweeps hit
the same cache.  The profiling layer attributes compilation **per
cache key** (:meth:`JaxBatchSimulator.dispatch` claims each distinct
jit signature exactly once), so concurrent dispatches — the streaming
service's normal mode — charge a compile to the bucket that actually
paid it; :func:`stepper_cache_size` still exposes the raw cache size.

**Sharding**: with more than one visible device the batch row axis is
partitioned across a 1-D ``("rows",)`` mesh with
``jax.shard_map`` — each device runs the vmapped
``while_loop`` on its own row shard *independently* (no per-wave
cross-device reduction: a shard whose rows finish early simply idles).
The row axis is padded to a shard multiple by replicating the last row
(results trimmed on fetch), bounds/schedules/policy state are
partitioned, and the geometry is partitioned (stacked layout) or
replicated (shared layout).  With one device the dispatch transparently
takes the original single-device vmap path.

**Async dispatch**: :meth:`JaxBatchSimulator.dispatch` returns as soon
as the stepper is enqueued (jax dispatch is asynchronous), so the sweep
engine packs and dispatches bucket *k+1* while bucket *k* computes;
:meth:`JaxBatchSimulator.fetch` then blocks and pulls the whole output
pytree to the host in ONE fused transfer (``jax.device_get``), never
one sync per field.  ``run()`` is ``fetch(dispatch())``.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.batchsim import (BatchArrays, GraphArrays, _frozen,
                                 build_graph_arrays, pad_bound_schedules,
                                 stack_graph_arrays, validate_padded_items)
from repro.core.graph import JobDependencyGraph
from repro.core.power import NodeSpec
from repro.core.simulator import OVER_BUDGET_RTOL, SimResult
from repro.obs import trace as obs_trace
from repro.kernels.power_step import (BIG_TIME, StepTables, power_step,
                                      step_tables)

from .policy_fns import (JaxPolicy, _JAX_REGISTRY, current_jobs,
                         get_jax_policy)
from .profile import BucketProfile

#: Anything above this is "no event" (see power_step's BIG_TIME).
_BIG_CUT = BIG_TIME * 0.5

#: Single fused device-to-host fetch (module alias so the one-sync-per-
#: run regression test can count calls).
_device_get = jax.device_get


class _Ctx(NamedTuple):
    """Traced per-batch geometry.

    In shared mode every leaf describes the one common (graph, cluster)
    and broadcasts over rows (``in_axes=None``); in stacked mode each
    leaf carries a leading row axis and is vmapped (``in_axes=0``) —
    except ``dt``, which is always the shared scalar tick.
    """

    tab: StepTables
    node_seq: jnp.ndarray    # (N, K) int32
    need: jnp.ndarray        # (J+1, N) int32 lane-progress thresholds
    work_pad: jnp.ndarray    # (J+1,)
    rho_pad: jnp.ndarray     # (J+1,)
    completed0: jnp.ndarray  # (J+1,) bool start state (phantoms born done)
    n_active: jnp.ndarray    # scalar int32: real node count
    dt: jnp.ndarray          # scalar (shared)


#: vmap ``in_axes`` for a stacked (per-row geometry) batch.
_CTX_ROW_AXES = _Ctx(
    tab=StepTables(*([0] * len(StepTables._fields))),
    node_seq=0, need=0, work_pad=0, rho_pad=0, completed0=0,
    n_active=0, dt=None)


class _RowState(NamedTuple):
    """One scenario row's loop carry."""

    ptr: jnp.ndarray        # (N,) int32 current-job pointer
    running: jnp.ndarray    # (N,) bool
    remaining: jnp.ndarray  # (N,)
    row_t: jnp.ndarray      # scalar
    bound: jnp.ndarray      # scalar *current* bound (schedules update it)
    sched_idx: jnp.ndarray  # scalar int32: next bound-schedule entry
    done: jnp.ndarray       # scalar bool
    stalled: jnp.ndarray    # scalar bool (deadlock flag)
    energy: jnp.ndarray     # scalar
    peak: jnp.ndarray       # scalar
    over_t: jnp.ndarray     # scalar
    makespan: jnp.ndarray   # scalar
    start_at: jnp.ndarray   # (N, K) start of each lane position, NaN until
    end_at: jnp.ndarray     # (N, K) end of each lane position, NaN until
    tick_count: jnp.ndarray  # scalar int32
    steps: jnp.ndarray      # scalar int32
    settle_rounds: jnp.ndarray  # scalar int32: _settle's rounds
    waterfill_rounds: jnp.ndarray  # scalar int32: the water-fill's rounds


def _cur(ctx: _Ctx, st: _RowState) -> jnp.ndarray:
    """Each lane's current job slot — shared with the policy layer
    (:func:`repro.backends.jax.policy_fns.current_jobs`)."""
    return current_jobs(ctx, st)


def _ready_mask(ctx: _Ctx, st: _RowState) -> jnp.ndarray:
    j = ctx.work_pad.shape[0] - 1
    cur = _cur(ctx, st)
    # lane progress against thresholds (see readiness_table): no
    # per-dependency gather into completed
    deps_ok = (st.ptr[None, :] >= ctx.need[cur]).all(axis=-1)
    return (~st.running) & (cur < j) & deps_ok & ~st.done


def _instant_mask(st: _RowState) -> jnp.ndarray:
    return st.running & (st.remaining <= 0.0)


def _start(ctx: _Ctx, st: _RowState, mask: jnp.ndarray) -> _RowState:
    return st._replace(
        running=st.running | mask,
        remaining=jnp.where(mask, ctx.work_pad[_cur(ctx, st)],
                            st.remaining))


def _complete(st: _RowState, mask: jnp.ndarray) -> _RowState:
    return st._replace(ptr=st.ptr + mask.astype(st.ptr.dtype),
                       running=st.running & ~mask)


def _started(st: _RowState) -> jnp.ndarray:
    """Jobs each lane has started: the completed ones and the running."""
    return st.ptr + st.running.astype(st.ptr.dtype)


#: Settle rounds one loop iteration runs.  The graph's work plays no
#: part, so a graph with its work zeroed (a warm-up) compiles the
#: stepper its real graph runs.
SETTLE_UNROLL = 4


def _settle(ctx: _Ctx, st: _RowState) -> _RowState:
    """Fixed point of everything that happens at the row's instant:
    start ready jobs, complete zero-work jobs, repeat until stable
    (mirrors ``BatchSimulator._settle``; policy caps are re-derived at
    the top of the next wave instead of via hooks).  A round evaluates
    the ready mask once, for its successor; its carry is lane state
    only.  Each loop iteration runs :data:`SETTLE_UNROLL` rounds, and a
    round after the fixed point changes nothing, so only the rounds
    that start or complete a job are counted in ``settle_rounds``."""

    def go(s, ready):
        return ready.any() | _instant_mask(s).any()

    def body(carry):
        s, ready, live = carry
        for _ in range(SETTLE_UNROLL):
            s = _start(ctx, s, ready)
            s = _complete(s, _instant_mask(s))
            s = s._replace(settle_rounds=s.settle_rounds
                           + live.astype(jnp.int32))
            ready = _ready_mask(ctx, s)
            live = go(s, ready)
        return s, ready, live

    ready = _ready_mask(ctx, st)
    st, _, _ = jax.lax.while_loop(lambda c: c[2], body,
                                  (st, ready, go(st, ready)))
    return st


def _stamp(ctx: _Ctx, st: _RowState, ptr0: jnp.ndarray,
           started0: jnp.ndarray) -> _RowState:
    """Close the row's instant: stamp ``row_t`` on the lane positions
    completed (from ``ptr0``) and started (from ``started0``) since the
    instant began, and mark the row done once every lane is exhausted
    (its current slot the sentinel)."""
    j = ctx.work_pad.shape[0] - 1
    pos = jnp.arange(ctx.node_seq.shape[-1])[None, :]

    def passed(lo, hi):
        return (pos >= lo[:, None]) & (pos < hi[:, None])

    all_done = (_cur(ctx, st) == j).all()
    newly = ~st.done & all_done
    return st._replace(
        start_at=jnp.where(passed(started0, _started(st)), st.row_t,
                           st.start_at),
        end_at=jnp.where(passed(ptr0, st.ptr), st.row_t, st.end_at),
        makespan=jnp.where(newly, st.row_t, st.makespan),
        done=st.done | all_done)


def _by_job(ctx: _Ctx, at: jnp.ndarray, fill) -> jnp.ndarray:
    """A ``(N, K)`` lane-position array laid out by job slot ``(J+1,)``;
    padding positions all land in the sentinel slot, which is junk."""
    base = jnp.full(ctx.work_pad.shape[0], fill, at.dtype)
    return base.at[ctx.node_seq].set(at)


def _row_loop(ctx: _Ctx, bound, sched_t, sched_w, pol_state, *,
              policy_name: str, wants_ticks: bool, redistribute: bool,
              max_steps: int):
    cls = _JAX_REGISTRY[policy_name]
    n = ctx.node_seq.shape[0]
    t_cols = sched_t.shape[0]
    ftype = ctx.work_pad.dtype
    zero = jnp.zeros((), ftype)
    st0 = _RowState(
        ptr=jnp.zeros(n, jnp.int32), running=jnp.zeros(n, bool),
        remaining=jnp.zeros(n, ftype),
        row_t=zero, bound=jnp.asarray(bound, ftype),
        sched_idx=jnp.zeros((), jnp.int32),
        done=jnp.zeros((), bool), stalled=jnp.zeros((), bool),
        energy=zero, peak=zero, over_t=zero, makespan=zero,
        start_at=jnp.full(ctx.node_seq.shape, jnp.nan, ftype),
        end_at=jnp.full(ctx.node_seq.shape, jnp.nan, ftype),
        tick_count=jnp.zeros((), jnp.int32), steps=jnp.zeros((), jnp.int32),
        settle_rounds=jnp.zeros((), jnp.int32),
        waterfill_rounds=jnp.zeros((), jnp.int32))
    st0 = _stamp(ctx, _settle(ctx, st0), st0.ptr, _started(st0))

    def cond(carry):
        st, _ = carry
        return ~st.done & ~st.stalled & (st.steps < max_steps)

    def body(carry):
        st, pol = carry
        ptr0, started0 = st.ptr, _started(st)
        caps = cls.caps_fn(ctx, st, pol)
        rate, t_fin, p_cluster, t_comp, wf_rounds = power_step(
            ctx.tab, caps.astype(ftype), st.running, st.remaining,
            ctx.rho_pad[_cur(ctx, st)], st.bound, redistribute)

        if wants_ticks:
            next_tick = (st.tick_count + 1).astype(ftype) * ctx.dt
            t_tick = next_tick - st.row_t
        else:
            next_tick = jnp.asarray(BIG_TIME, ftype)
            t_tick = next_tick
        # next scheduled cluster-bound arrival (padded with BIG_TIME;
        # sched_live guards re-reading a consumed final entry)
        idx_c = jnp.minimum(st.sched_idx, t_cols - 1)
        sched_live = st.sched_idx < t_cols
        next_bound_t = sched_t[idx_c]
        t_bound = jnp.where(sched_live, next_bound_t - st.row_t,
                            jnp.asarray(BIG_TIME, ftype))
        delta = jnp.minimum(jnp.minimum(t_comp, t_tick), t_bound)
        # Deadlock is judged on t_comp, not delta: starts depend only on
        # dependency completions, so a row with no running lane can
        # never recover — bound arrivals and policy ticks cannot start
        # jobs either.
        stalled_now = t_comp >= _BIG_CUT
        delta = jnp.where(stalled_now, 0.0, delta)
        # over-budget classification uses the bound in effect *during*
        # the wave; a scheduled change applies from its arrival onwards
        over = p_cluster > st.bound * (1 + OVER_BUDGET_RTOL) + 1e-9
        finishing = st.running & (t_fin <= delta * (1 + 1e-6) + 1e-9)
        row_t = st.row_t + delta
        due = (t_tick <= t_comp) & (t_tick <= t_bound) & ~stalled_now \
            if wants_ticks else jnp.zeros((), bool)
        row_t = jnp.where(due, next_tick, row_t)   # kill the float residue
        bound_due = sched_live & (t_bound <= t_comp) & (t_bound <= t_tick) \
            & ~stalled_now
        row_t = jnp.where(bound_due, next_bound_t, row_t)
        st = st._replace(
            remaining=jnp.where(finishing, 0.0,
                                st.remaining - rate * delta),
            row_t=row_t,
            bound=jnp.where(bound_due, sched_w[idx_c], st.bound),
            sched_idx=st.sched_idx + bound_due.astype(jnp.int32),
            energy=st.energy + p_cluster * delta,
            peak=jnp.maximum(st.peak, p_cluster),
            over_t=st.over_t + jnp.where(over, delta, 0.0),
            stalled=st.stalled | stalled_now,
            steps=st.steps + 1,
            waterfill_rounds=st.waterfill_rounds + wf_rounds)
        st = _complete(st, finishing)
        if wants_ticks:
            pol = cls.tick_fn(ctx, st, pol, due)
            st = st._replace(
                tick_count=st.tick_count + due.astype(jnp.int32))
        st = _stamp(ctx, _settle(ctx, st), ptr0, started0)
        return st, pol

    st, _ = jax.lax.while_loop(cond, body, (st0, pol_state))
    done_at = jnp.arange(ctx.node_seq.shape[-1])[None, :] < st.ptr[:, None]
    return {
        "makespan": st.makespan, "energy": st.energy, "peak": st.peak,
        "over_t": st.over_t,
        "start_t": _by_job(ctx, st.start_at, jnp.nan),
        "end_t": _by_job(ctx, st.end_at, jnp.nan),
        "completed": ctx.completed0 | _by_job(ctx, done_at, False),
        "done": st.done, "stalled": st.stalled,
        "steps": st.steps, "settle_rounds": st.settle_rounds,
        "waterfill_rounds": st.waterfill_rounds,
    }


_STATIC_ARGS = ("policy_name", "wants_ticks", "redistribute",
                "max_steps", "stacked")


def _vmapped_rows(ctx: _Ctx, bounds, sched_t, sched_w, pol_state, *,
                  policy_name: str, wants_ticks: bool, redistribute: bool,
                  max_steps: int, stacked: bool):
    """The stepper vmapped over the (local) row axis — the shared body
    of the single-device and per-shard paths."""
    row = functools.partial(
        _row_loop, policy_name=policy_name, wants_ticks=wants_ticks,
        redistribute=redistribute, max_steps=max_steps)
    ctx_axes = _CTX_ROW_AXES if stacked else None
    return jax.vmap(lambda c, b, t, w, p: row(c, b, t, w, p),
                    in_axes=(ctx_axes, 0, 0, 0, 0))(
        ctx, bounds, sched_t, sched_w, pol_state)


# No donate_argnums on the steppers: the output pytree (row scalars +
# job stamps) is far smaller than any input and can never alias one, so
# XLA would reject every donation with a warning per dispatch.
@functools.partial(jax.jit, static_argnames=_STATIC_ARGS)
def _run_batch(ctx: _Ctx, bounds, sched_t, sched_w, pol_state, *,
               policy_name: str, wants_ticks: bool, redistribute: bool,
               max_steps: int, stacked: bool):
    return _vmapped_rows(
        ctx, bounds, sched_t, sched_w, pol_state,
        policy_name=policy_name, wants_ticks=wants_ticks,
        redistribute=redistribute, max_steps=max_steps, stacked=stacked)


@functools.lru_cache(maxsize=None)
def _row_mesh(n_shards: int) -> Mesh:
    """The 1-D device mesh the row axis shards over."""
    return Mesh(np.array(jax.devices()[:n_shards]), ("rows",))


def _ctx_specs(stacked: bool) -> _Ctx:
    """shard_map partition specs for the geometry pytree: every leaf is
    row-partitioned in the stacked layout (it carries a leading row
    axis) and replicated in the shared layout; ``dt`` is always the
    shared scalar."""
    rows, rep = P("rows"), P()
    leaf = rows if stacked else rep
    return _Ctx(tab=StepTables(*([leaf] * len(StepTables._fields))),
                node_seq=leaf, need=leaf, work_pad=leaf,
                rho_pad=leaf, completed0=leaf, n_active=leaf, dt=rep)


@functools.partial(jax.jit,
                   static_argnames=_STATIC_ARGS + ("n_shards",))
def _run_batch_sharded(ctx: _Ctx, bounds, sched_t, sched_w, pol_state, *,
                       policy_name: str, wants_ticks: bool,
                       redistribute: bool, max_steps: int, stacked: bool,
                       n_shards: int):
    """The stepper with the row axis sharded over ``n_shards`` devices.

    Each shard runs its own vmapped ``while_loop`` to completion with
    no cross-device synchronization inside the loop (``check_vma`` off:
    the outputs are row-partitioned by construction).  Callers pad the
    row axis to a multiple of ``n_shards`` first.
    """
    body = functools.partial(
        _vmapped_rows, policy_name=policy_name, wants_ticks=wants_ticks,
        redistribute=redistribute, max_steps=max_steps, stacked=stacked)
    rows = P("rows")
    return jax.shard_map(body, mesh=_row_mesh(n_shards),
                         in_specs=(_ctx_specs(stacked), rows, rows, rows,
                                   rows),
                         out_specs=rows, check_vma=False)(
        ctx, bounds, sched_t, sched_w, pol_state)


def shard_count(requested: Optional[int], n_rows: int) -> int:
    """Resolve a shard-device request against the visible devices and
    the batch size: ``None`` means every visible device, and a batch
    never shards wider than its row count (a 3-row batch on 8 devices
    runs 3-wide, not 8-wide with 5 idle phantom shards)."""
    avail = len(jax.devices())
    n = avail if requested is None else min(int(requested), avail)
    return max(1, min(n, n_rows))


def stepper_cache_size() -> int:
    """Total compiled-stepper cache entries (both dispatch paths)."""
    return _run_batch._cache_size() + _run_batch_sharded._cache_size()


#: Stepper cache keys this process has already dispatched (and hence
#: compiled).  Compilation is attributed **per key**, never from a
#: global cache-size delta around one dispatch: when several batches
#: are dispatched concurrently — the streaming service's normal mode —
#: another dispatch's compile would land inside this bucket's sampling
#: window and be charged to the wrong profile.
_compiled_keys: set = set()
_compiled_keys_lock = threading.Lock()


def _claim_cache_key(key: tuple) -> bool:
    """True when ``key`` was not seen before (this dispatch compiles);
    marks it seen atomically so concurrent dispatches of one new key
    attribute its compilation exactly once."""
    with _compiled_keys_lock:
        if key in _compiled_keys:
            return False
        _compiled_keys.add(key)
        return True


def _pad_rows(pad: int, *arrays):
    """Grow each array's leading (row) axis by ``pad`` replicas of its
    last row — the sharded path's phantom rows, trimmed on fetch."""
    if pad <= 0:
        return arrays
    return tuple(np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
                 for a in arrays)


def wave_counts(steps: np.ndarray, n_rows: int,
                n_shards: int) -> Tuple[int, int, int]:
    """``(waves, row_waves, row_slots)`` of one fetched batch from its
    rows' ``steps`` (the row axis as dispatched, phantom rows included).

    Each shard runs one ``while_loop`` over its contiguous block of
    rows until its slowest row is done, so ``waves`` — the lockstep
    iterations — is the most ``steps`` of each shard's real rows,
    summed over the shards; ``row_waves`` is the sum over the real
    rows, and ``row_slots`` each shard's real rows times its waves,
    summed.  The shard-padding phantom rows (after ``n_rows``) count in
    none of them.
    """
    per = len(steps) // n_shards
    real = steps[:n_rows]
    shards = [real[s:s + per] for s in range(0, n_rows, per)]
    return (sum(int(b.max()) for b in shards), int(real.sum()),
            sum(len(b) * int(b.max()) for b in shards))


def _to_device(x):
    """Normalize dtypes host-side; the jit boundary does the transfer."""
    a = np.asarray(x)
    if a.dtype.kind == "f":
        return a.astype(np.dtype(jnp.result_type(float).name), copy=False)
    if a.dtype.kind == "i":
        return a.astype(np.int32, copy=False)
    return a


def readiness_table(node_seq: np.ndarray,
                    deps_pad: np.ndarray) -> np.ndarray:
    """Per-job lane-progress thresholds: the stepper's dependency test.

    A lane completes its jobs in ``node_seq`` order and its pointer
    ``ptr[m]`` counts the ones done, so job ``X`` at position ``p`` of
    lane ``m`` is complete exactly when ``ptr[m] > p``.  Entry
    ``need[k, m]`` is therefore 1 + the highest lane-``m`` position
    among job ``k``'s dependencies (0 where it has none there), and
    job ``k`` may start once ``ptr >= need[k]`` on every lane.

    Takes the shared ``(N, K)``/``(J+1, D)`` or the stacked
    ``(B, N, K)``/``(B, J+1, D)`` arrays, with ``J`` the sentinel slot,
    and returns ``(J+1, N)`` or ``(B, J+1, N)`` int32.  Sentinel and
    padding dependencies contribute 0, as do phantom job slots.
    """
    stacked = node_seq.ndim == 3
    if not stacked:
        node_seq, deps_pad = node_seq[None], deps_pad[None]
    b, n, _ = node_seq.shape
    j1, d = deps_pad.shape[1:]
    # every real job slot's lane and 1-based position, flat over
    # (row, slot); the sentinel and phantom slots keep lane 0, position
    # 0, so a dependency on them requires nothing
    lane = np.zeros(b * j1, np.int64)
    pos1 = np.zeros(b * j1, np.int32)
    r, m, p = np.nonzero(node_seq < j1 - 1)
    slot = r * j1 + node_seq[r, m, p]
    lane[slot] = m
    pos1[slot] = p + 1
    deps = (deps_pad + (np.arange(b) * j1)[:, None, None]).reshape(-1, d)
    base = np.arange(b * j1) * n      # flat offset of each (row, job)
    need = np.zeros(b * j1 * n, np.int32)
    # one column at a time: within a column each (row, job) appears
    # once, so each (row, job, lane) target is written once
    for c in range(d):
        dep = deps[:, c]
        tgt = base + lane[dep]
        need[tgt] = np.maximum(need[tgt], pos1[dep])
    need = need.reshape(b, j1, n)
    return need if stacked else need[0]


class _Pending(NamedTuple):
    """An in-flight dispatched batch: device-resident outputs plus the
    accumulating profile (see :meth:`JaxBatchSimulator.dispatch`)."""

    out: Dict[str, jnp.ndarray]
    profile: BucketProfile


class JaxBatchSimulator:
    """Compiled drop-in for :class:`~repro.core.batchsim.BatchSimulator`.

    Same two batch layouts — the constructor's fixed-structure batch
    (one graph, one cluster, B bounds, one policy) and :meth:`padded`'s
    mixed-shape stacked batch — with ``policy`` resolved from the
    jax-policy registry (:mod:`repro.backends.jax.policy_fns`).
    ``bound_schedules`` (one ``(time_s, bound_w)`` iterable per row)
    makes the rows' cluster bounds time-varying, resolved at exact
    arrival times inside the compiled loop.  ``shard_devices`` shards
    the batch row axis across that many visible devices (``None`` = all
    of them; with one device the single-device vmap path runs
    unchanged).  Power traces are not
    retained (``trace_every`` must be ``None``): sweeps that need
    traces belong on the numpy backends.
    """

    def __init__(self, graph: JobDependencyGraph, specs: Sequence[NodeSpec],
                 bounds: Sequence[float],
                 policy: Union[str, JaxPolicy] = "equal-share",
                 dt: float = 0.05, latency_s: float = 0.05,
                 trace_every: Optional[float] = None,
                 max_steps: int = 1_000_000,
                 bound_schedules: Optional[Sequence] = None,
                 shard_devices: Optional[int] = None,
                 **policy_kwargs):
        graph.topological_order()          # validates the DAG
        if len(specs) != len(graph.nodes):
            raise ValueError("one NodeSpec per graph node required")
        self.graph = graph
        self.specs = list(specs)
        self._setup_run_params(bounds, policy, dt, latency_s, trace_every,
                               max_steps, policy_kwargs, bound_schedules,
                               shard_devices)
        b = self.n_rows
        arrays = build_graph_arrays(graph, self.specs)
        self._init_rows(
            arrays, stacked=False,
            row_graphs=[graph] * b, row_specs=[self.specs] * b,
            row_job_ids=(tuple(arrays.job_ids),) * b,
            n_jobs_row=np.full(b, arrays.n_jobs),
            n_active=np.full(b, arrays.n_nodes),
            need=graph.derived("jax.need", lambda g: _frozen(
                readiness_table(arrays.node_seq, arrays.deps_pad))))

    @classmethod
    def padded(cls, items: Sequence[Tuple[JobDependencyGraph,
                                          Sequence[NodeSpec]]],
               bounds: Sequence[float],
               policy: Union[str, JaxPolicy] = "equal-share",
               dt: float = 0.05, latency_s: float = 0.05,
               trace_every: Optional[float] = None,
               max_steps: int = 1_000_000,
               bound_schedules: Optional[Sequence] = None,
               pad_dims: Optional[Tuple[int, int, int, int, int]] = None,
               shard_devices: Optional[int] = None,
               **policy_kwargs) -> "JaxBatchSimulator":
        """Build a mixed-shape compiled batch: row ``b`` runs
        ``items[b]`` under ``bounds[b]`` (see
        :meth:`repro.core.batchsim.BatchSimulator.padded` for the
        padding contract and ``pad_dims``)."""
        self = cls.__new__(cls)
        items, bounds = validate_padded_items(items, bounds)
        self.graph = None
        self.specs = None
        self._setup_run_params(bounds, policy, dt, latency_s, trace_every,
                               max_steps, policy_kwargs, bound_schedules,
                               shard_devices)
        arrays = stack_graph_arrays(items, pad_dims)
        self._init_rows(
            arrays, stacked=True,
            row_graphs=[g for g, _ in items],
            row_specs=[list(sp) for _, sp in items],
            row_job_ids=arrays.row_job_ids,
            n_jobs_row=arrays.n_jobs_row, n_active=arrays.n_active)
        return self

    # ------------------------------------------------------- construction
    def _init_rows(self, arrays, *, stacked, row_graphs, row_specs,
                   row_job_ids, n_jobs_row, n_active, need=None) -> None:
        """One home for the per-row bookkeeping both layouts must fill
        (mirrors ``BatchSimulator._init_geometry`` — policies rely on
        these attributes being layout-agnostic).  ``need`` is the
        readiness table when the caller kept one (the shared layout
        keeps it with its graph), else it is built here."""
        self.arrays = arrays
        self.stacked = stacked
        self.row_graphs = row_graphs
        self.row_specs = row_specs
        self.row_job_ids = row_job_ids
        self.n_jobs_row = n_jobs_row
        self.n_active = n_active
        self.n_jobs_total = arrays.n_jobs
        # built here, under the sweep's build region, not per pack
        self.need = need if need is not None else readiness_table(
            arrays.node_seq, arrays.deps_pad)

    def _setup_run_params(self, bounds, policy, dt, latency_s, trace_every,
                          max_steps, policy_kwargs, bound_schedules,
                          shard_devices=None) -> None:
        if dt <= 0:
            raise ValueError("dt must be positive")
        if trace_every is not None:
            raise ValueError("the jax backend retains no power traces "
                             "(trace_every must be None); use the vector "
                             "or event backend for traced runs")
        self.bounds = np.asarray(list(bounds), dtype=float)
        if self.bounds.ndim != 1 or len(self.bounds) == 0:
            raise ValueError("bounds must be a non-empty 1-D sequence")
        self.dt = float(dt)
        self.latency_s = float(latency_s)
        self.max_steps = int(max_steps)
        self.n_shards = shard_count(shard_devices, len(self.bounds))
        self._sched = pad_bound_schedules(bound_schedules, len(self.bounds))
        if isinstance(policy, JaxPolicy):
            if policy_kwargs:
                raise ValueError("policy_kwargs only apply to registry "
                                 "keys")
            self.policy = policy
        else:
            self.policy = get_jax_policy(policy, **policy_kwargs)

    @property
    def n_rows(self) -> int:
        return len(self.bounds)

    @property
    def n_nodes(self) -> int:
        return self.arrays.n_nodes

    def _ctx(self) -> _Ctx:
        # numpy leaves throughout: the jitted stepper converts the whole
        # pytree in one dispatch, instead of ~15 eager device_puts here.
        a = self.arrays
        j = self.n_jobs_total
        ftype = np.dtype(jnp.result_type(float).name)
        if self.stacked:
            completed0 = np.zeros((self.n_rows, j + 1), dtype=bool)
            completed0[:, j] = True
            completed0[:, :j] |= \
                np.arange(j)[None, :] >= self.n_jobs_row[:, None]
            n_active = np.asarray(self.n_active, np.int32)
        else:
            completed0 = np.zeros(j + 1, dtype=bool)
            completed0[j] = True
            n_active = np.asarray(a.n_nodes, np.int32)
        return _Ctx(tab=step_tables(a.table, ftype),
                    node_seq=np.asarray(a.node_seq, np.int32),
                    need=self.need,
                    work_pad=np.asarray(a.work_pad, ftype),
                    rho_pad=np.asarray(a.rho_pad, ftype),
                    completed0=completed0, n_active=n_active,
                    dt=np.asarray(self.dt, ftype))

    def _pack(self) -> Tuple[tuple, Dict[str, object]]:
        """The stepper's operands, as host arrays with the row axis
        padded to the shard width, and its static config: what
        :meth:`dispatch` hands to the jitted stepper."""
        self.policy.prepare(self)
        pol_state = {k: _to_device(v)
                     for k, v in self.policy.init_state(self).items()}
        if self._sched is not None:
            sched_t, sched_w = self._sched
        else:
            sched_t = np.full((self.n_rows, 1), BIG_TIME)
            sched_w = np.zeros((self.n_rows, 1))
        ctx = self._ctx()
        bounds = self.bounds
        pad = (-self.n_rows) % self.n_shards
        if pad:
            bounds, sched_t, sched_w = _pad_rows(pad, bounds, sched_t,
                                                 sched_w)
            pol_state = self.policy.pad_state_rows(pol_state, pad)
            if self.stacked:
                ctx = ctx._replace(
                    tab=StepTables(*_pad_rows(pad, *ctx.tab)),
                    node_seq=_pad_rows(pad, ctx.node_seq)[0],
                    need=_pad_rows(pad, ctx.need)[0],
                    work_pad=_pad_rows(pad, ctx.work_pad)[0],
                    rho_pad=_pad_rows(pad, ctx.rho_pad)[0],
                    completed0=_pad_rows(pad, ctx.completed0)[0],
                    n_active=_pad_rows(pad, ctx.n_active)[0])
        statics = dict(
            policy_name=self.policy.name,
            wants_ticks=self.policy.wants_ticks,
            redistribute=self.policy.redistribute,
            max_steps=self.max_steps,
            stacked=self.stacked)
        return (ctx, _to_device(bounds), _to_device(sched_t),
                _to_device(sched_w), pol_state), statics

    def dispatch(self, bucket: str = "?") -> _Pending:
        """Pack, pad, and *asynchronously* launch the compiled batch.

        Returns as soon as the stepper is enqueued on the device(s):
        the caller overlaps host work (packing the next bucket) with
        the device compute and collects results later with
        :meth:`fetch`.  The profile records the host packing time, the
        dispatch wall-clock, and — when this dispatch is the first for
        its jit cache key — the compile time it paid (a cache hit
        dispatches in microseconds, so the dispatch wall *is* the
        compile on a miss).  Attribution is per cache key, so
        concurrent dispatches never charge a compile to the wrong
        bucket.  ``bucket`` labels the profile and the regions of
        this batch's dispatch and fetch (``repro.engine.*``, see
        :func:`repro.obs.trace.region`), whichever thread runs them.
        """
        prof = BucketProfile(bucket=bucket, rows=self.n_rows,
                             devices=self.n_shards)
        # The profile's timers run inside the regions, so they time the
        # same work with tracing on or off.
        with obs_trace.region("pack", "engine", chrome="pack",
                              bucket=bucket, rows=self.n_rows,
                              devices=self.n_shards):
            t0 = time.perf_counter()
            args, statics = self._pack()
            ctx, bounds, sched_t, _, pol_state = args
            # The full jit identity of this dispatch: every traced
            # operand shape (geometry envelope, padded row count,
            # schedule columns, policy-state leaves) plus the static
            # config.  Two dispatches share a compiled stepper iff
            # their keys are equal, so the per-key compile attribution
            # below is exact even when batches dispatch concurrently.
            prof.cache_key = (
                (ctx.work_pad.shape, ctx.node_seq.shape,
                 np.shape(bounds), np.shape(sched_t),
                 tuple(sorted((k, np.shape(v))
                              for k, v in pol_state.items())),
                 self.n_shards, self.policy.name)
                + tuple(sorted(statics.items())))
            prof.pack_s = time.perf_counter() - t0
        prof.compiled = _claim_cache_key(prof.cache_key)
        # Regions are host-side only: they cannot perturb the jit key.
        with obs_trace.region("dispatch", "engine",
                              chrome="compile" if prof.compiled
                              else "dispatch",
                              bucket=bucket, rows=self.n_rows,
                              devices=self.n_shards,
                              compiled=prof.compiled):
            t1 = time.perf_counter()
            if self.n_shards > 1:
                out = _run_batch_sharded(*args, n_shards=self.n_shards,
                                         **statics)
            else:
                out = _run_batch(*args, **statics)
            prof.dispatch_s = time.perf_counter() - t1
        prof.compile_s = prof.dispatch_s if prof.compiled else 0.0
        return _Pending(out=out, profile=prof)

    def fetch(self, pending: _Pending) -> List[SimResult]:
        """Block on a dispatched batch and build its results.

        The whole output pytree comes back in ONE fused device-to-host
        transfer (``jax.device_get``) — never one sync per field — and
        shard-padding phantom rows are trimmed before any bookkeeping.
        The profile counts the batch's waves from the rows' ``steps``,
        their settle rounds from ``settle_rounds`` and their water-fill
        rounds from ``waterfill_rounds``.
        """
        prof = pending.profile
        args = {"bucket": prof.bucket, "rows": self.n_rows,
                "devices": self.n_shards}
        with obs_trace.region("wait", "engine", chrome="run", **args):
            t0 = time.perf_counter()
            jax.block_until_ready(pending.out)
            prof.run_s = time.perf_counter() - t0
        with obs_trace.region("transfer", "engine", chrome="transfer",
                              **args):
            t1 = time.perf_counter()
            out = _device_get(pending.out)
            prof.transfer_s = time.perf_counter() - t1
        prof.waves, prof.row_waves, prof.row_slots = wave_counts(
            np.asarray(out["steps"]), self.n_rows, self.n_shards)
        prof.settle_rounds = int(
            np.asarray(out["settle_rounds"])[:self.n_rows].sum())
        prof.waterfill_rounds = int(
            np.asarray(out["waterfill_rounds"])[:self.n_rows].sum())
        with obs_trace.region("results", "engine", waves=prof.waves,
                              row_waves=prof.row_waves,
                              row_slots=prof.row_slots,
                              settle_rounds=prof.settle_rounds,
                              waterfill_rounds=prof.waterfill_rounds,
                              **args):
            out = {k: np.asarray(v)[:self.n_rows] for k, v in out.items()}
            self._check_failures(out)
            return self._results(out)

    def run(self) -> List[SimResult]:
        """Dispatch and immediately fetch (the synchronous facade)."""
        return self.fetch(self.dispatch())

    def _check_failures(self, out: Dict[str, np.ndarray]) -> None:
        if out["stalled"].any():
            bad = int(np.nonzero(out["stalled"])[0][0])
            jids = self.row_job_ids[bad]
            missing = [jids[k] for k in range(int(self.n_jobs_row[bad]))
                       if not out["completed"][bad, k]]
            raise RuntimeError(f"deadlock in batch row {bad}: jobs "
                               f"never ran: {sorted(missing)[:8]}")
        hung = ~out["done"] & (out["steps"] >= self.max_steps)
        if hung.any():
            raise RuntimeError(f"jax batch simulator exceeded max steps "
                               f"({self.max_steps}); livelock?")

    def _results(self, out: Dict[str, np.ndarray]) -> List[SimResult]:
        name = self.policy.name
        results: List[SimResult] = []

        def stamps(job_ids, t):
            """Job id -> stamp, jobs never stamped (NaN) left out."""
            t = t[:len(job_ids)]
            seen = ~np.isnan(t)
            return dict(zip(itertools.compress(job_ids, seen),
                            t[seen].tolist()))

        for row in range(self.n_rows):
            job_ids = self.row_job_ids[row]
            makespan = float(out["makespan"][row])
            starts = stamps(job_ids, out["start_t"][row])
            ends = stamps(job_ids, out["end_t"][row])
            energy = float(out["energy"][row])
            results.append(SimResult(
                policy=name, makespan=makespan, energy_j=energy,
                avg_power_w=energy / makespan if makespan > 0 else 0.0,
                peak_power_w=float(out["peak"][row]),
                over_budget_time=float(out["over_t"][row]),
                messages=0, distributes=0, suppressed_reports=0,
                power_trace=[], job_starts=starts, job_ends=ends))
        return results


def simulate_batch_jax(graph: JobDependencyGraph,
                       specs: Sequence[NodeSpec],
                       bounds: Sequence[float],
                       policy: Union[str, JaxPolicy] = "equal-share",
                       dt: float = 0.05, latency_s: float = 0.05,
                       **kwargs) -> List[SimResult]:
    """One-call facade: one :class:`SimResult` per entry of ``bounds``."""
    return JaxBatchSimulator(graph, specs, bounds, policy=policy, dt=dt,
                             latency_s=latency_s, **kwargs).run()
