"""Jittable policy functions for the compiled JAX engine.

The numpy batch backend drives :class:`~repro.policies.vector.VectorPolicy`
objects that mutate ``sim.cap`` from Python hooks; inside a
``jax.lax.while_loop`` there are no Python hooks, so the compiled engine
re-expresses each policy as pure functions of the wave state:

* ``caps_fn(ctx, st, pol) -> (N,) watts`` — evaluated at the top of
  every wave from the *post-settle* state.  Because waves land exactly
  on state transitions, recomputing event-driven caps every wave is
  semantically identical to the event hooks for the exact policies
  (equal-share, ilp, ilp-makespan, oracle).
* ``tick_fn(ctx, st, pol, due) -> pol`` — the only quantized hook;
  fires when a ``dt`` boundary wins the wave (``wants_ticks`` policies).

Host-side work that cannot be traced (ILP solves) happens once in
``prepare``/``init_state``, which bake their results into the per-row
policy-state pytree the engine carries through the loop.  ``caps_fn`` /
``tick_fn`` are staticmethods referenced by registry *name* inside the
jitted stepper, so recreating a policy object never retriggers
compilation.

``exact`` has the same meaning as in the vector registry and the
differential suite holds jax results to the same ``2*dt`` / 1%
envelopes; the tick-quantized ``heuristic`` stays ``exact=False``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.kernels.power_step import reclaim_waterfill
from repro.policies.registry import PolicyRegistry
from repro.policies.vector import resolve_assignments


def current_jobs(ctx, st) -> jnp.ndarray:
    """Each lane's current job slot (sentinel ``J`` when exhausted).

    The one gather every job-indexed policy needs (ILP per-job caps,
    the learned policy's per-job ``cpu_frac`` feature) and the engine's
    own physics; shared here so the slot convention cannot drift."""
    n = ctx.node_seq.shape[0]
    return ctx.node_seq[jnp.arange(n), st.ptr]


def _nominal(ctx, st) -> jnp.ndarray:
    """The paper's P/n share as a lane vector.

    ``n`` is the row's *real* node count (``ctx.n_active``) — in a
    padded mixed-shape batch the lane axis is wider, but phantom lanes
    never run, so their nominal cap is inert.  ``st.bound`` is the
    row's *current* bound, so a scheduled bound change re-splits
    immediately (the event equal-share's ``on_bound_change``)."""
    n = ctx.node_seq.shape[0]
    share = st.bound / ctx.n_active
    return jnp.broadcast_to(share, (n,)).astype(jnp.result_type(st.bound))


class JaxPolicy:
    """Base class: static nominal caps, no state, no ticks.

    Subclasses override the *host-side* hooks (``prepare`` once per
    batch, ``init_state`` for the per-row state pytree) and the *traced*
    staticmethods ``caps_fn`` / ``tick_fn``.  ``redistribute=True``
    delegates cap computation to the wave step's reclamation /
    water-fill stage instead of ``caps_fn`` (the oracle rule).
    """

    name: str = "?"
    exact: bool = True
    wants_ticks: bool = False
    redistribute: bool = False

    def prepare(self, sim) -> None:
        """One-time host-side setup (may solve ILPs); ``sim`` is the
        owning :class:`~repro.backends.jax.engine.JaxBatchSimulator`."""

    def init_state(self, sim) -> Dict[str, np.ndarray]:
        """Per-row policy-state pytree, batched over rows (leading B).

        Every leaf MUST carry the batch row axis first: the sharded
        executor partitions axis 0 across devices and pads it to a
        shard multiple (:meth:`pad_state_rows`), so a leaf without the
        row axis would be silently mis-sharded."""
        return {}

    @staticmethod
    def pad_state_rows(state: Dict[str, np.ndarray],
                       pad: int) -> Dict[str, np.ndarray]:
        """Grow the state's row axis by ``pad`` phantom rows (the
        sharded engine rounds the batch up to a multiple of the device
        count).  The default replicates the last row — correct for any
        state whose rows are independent, which the per-row stepper
        guarantees; the phantom rows' results are discarded."""
        if pad <= 0 or not state:
            return state
        return {k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k, v in state.items()}

    @staticmethod
    def caps_fn(ctx, st, pol) -> jnp.ndarray:
        return _nominal(ctx, st)

    @staticmethod
    def tick_fn(ctx, st, pol, due):
        return pol


_JAX_REGISTRY = PolicyRegistry(JaxPolicy, "jax")


def register_jax_policy(name: str, *aliases: str):
    """Class decorator: register a jax-policy factory under ``name``."""
    return _JAX_REGISTRY.register(name, *aliases)


def get_jax_policy(name: str, **kwargs) -> "JaxPolicy":
    return _JAX_REGISTRY.get(name, **kwargs)


def has_jax_policy(name: str) -> bool:
    return name in _JAX_REGISTRY


def jax_policies() -> List[str]:
    return _JAX_REGISTRY.names()


@register_jax_policy("equal-share", "equal_share")
class JaxEqualShare(JaxPolicy):
    """Static P/n caps — the base class is the whole policy."""

    name = "equal-share"


@register_jax_policy("ilp")
class JaxIlpStatic(JaxPolicy):
    """Static per-job ILP caps, gathered at each lane's current job.

    The event/vector backends apply the cap at job start and leave it
    in place between jobs; gathering ``caps_job[cur]`` every wave gives
    the same physics (non-running lanes draw idle power regardless of
    their cap).  ``assignments`` is one pre-solved
    :class:`~repro.core.ilp.PowerAssignment` per batch row (the sweep
    engine's shared-setup cache); missing entries are solved in
    ``prepare``, once per unique bound.
    """

    name = "ilp"
    use_makespan_milp = False

    def __init__(self, assignments: Optional[Sequence] = None,
                 time_limit: float = 60.0):
        self.assignments = assignments
        self.time_limit = time_limit

    def _solve(self, sim, row: int, bound_w: float):
        from repro.core.ilp import build_makespan_milp, solve_paper_ilp

        solver = (build_makespan_milp if self.use_makespan_milp
                  else solve_paper_ilp)
        return solver(sim.row_graphs[row], sim.row_specs[row], bound_w,
                      time_limit=self.time_limit)

    def init_state(self, sim) -> Dict[str, np.ndarray]:
        j = sim.n_jobs_total
        resolved = resolve_assignments(
            sim.bounds, self.assignments,
            lambda row, bound: self._solve(sim, row, bound),
            graphs=sim.row_graphs)
        caps_job = np.zeros((sim.n_rows, j + 1))
        for b, assignment in enumerate(resolved):
            for k, jid in enumerate(sim.row_job_ids[b]):
                caps_job[b, k] = assignment.bounds_w[jid]
            # sentinel slot: exhausted lanes gather the nominal share
            caps_job[b, j] = sim.bounds[b] / sim.n_active[b]
        return {"caps_job": caps_job}

    @staticmethod
    def caps_fn(ctx, st, pol) -> jnp.ndarray:
        n = ctx.node_seq.shape[0]
        cur = ctx.node_seq[jnp.arange(n), st.ptr]
        return pol["caps_job"][cur]


@register_jax_policy("ilp-makespan")
class JaxIlpMakespan(JaxIlpStatic):
    name = "ilp-makespan"
    use_makespan_milp = True

    def __init__(self, assignments: Optional[Sequence] = None,
                 time_limit: float = 120.0):
        super().__init__(assignments=assignments, time_limit=time_limit)


@register_jax_policy("oracle")
class JaxOracle(JaxPolicy):
    """Zero-latency clairvoyant water-filling.

    ``redistribute=True``: the wave step reclaims non-running
    lanes' idle draw and water-fills the rest every wave, which at
    exact event times reproduces the event oracle's cap trajectory —
    ``caps_fn`` is never consulted for physics.
    """

    name = "oracle"
    redistribute = True


@register_jax_policy("learned")
class JaxLearned(JaxPolicy):
    """Gradient-trained MLP cap split, compiled.

    The math is the shared xp-generic core in
    :mod:`repro.policies.learned` called with ``jax.numpy`` — the same
    functions the event/vector adapters run with numpy and
    :mod:`repro.diff.train` differentiates through, so the trained
    parameters mean the same thing in every backend.  Checkpoint weights
    are tiled across the row axis in ``init_state`` (every leaf carries
    the batch dimension the sharded executor partitions); the per-row
    ``caps_fn`` sees the plain ``(F, H)`` matrices after vmap strips it.
    ``exact=False``: the engine evaluates the MLP in float32, and near
    an LUT state-power threshold that rounding can flip the selected
    operating point versus the float64 vector adapter.
    """

    name = "learned"
    exact = False

    def __init__(self, checkpoint: Optional[str] = None):
        from repro.policies.learned import load_checkpoint

        self.params = load_checkpoint(checkpoint)

    def init_state(self, sim) -> Dict[str, np.ndarray]:
        b = sim.n_rows
        return {f"mlp_{k}": np.repeat(np.asarray(v)[None], b, axis=0)
                for k, v in self.params.items()}

    @staticmethod
    def caps_fn(ctx, st, pol) -> jnp.ndarray:
        from repro.policies.learned import compute_caps

        params = {k[4:]: v for k, v in pol.items()
                  if k.startswith("mlp_")}
        rho = ctx.rho_pad[current_jobs(ctx, st)]
        return compute_caps(
            jnp, params, running=st.running,
            rho=jnp.where(st.running, rho, 0.0),
            bound=st.bound * 1.0, n_active=ctx.n_active * 1.0,
            p_max=ctx.tab.p_max, cap_floor=ctx.tab.cap_floor,
            idle_w=ctx.tab.idle_w)


@register_jax_policy("heuristic")
class JaxOnlineHeuristic(JaxPolicy):
    """Tick-quantized online redistribution (vector-heuristic semantics).

    Each due tick water-fills the cluster bound (minus blocked lanes'
    idle draw) over the running lanes and pushes the target into a
    per-row ring buffer; the cap matrix applied to the row is the
    target from ``delay`` ticks ago (report + distribute latency
    rounded to whole ticks), reproducing the paper's transient surges
    above the bound.  Same control plane as
    :class:`~repro.policies.vector.VectorOnlineHeuristic`, so the same
    ``exact=False`` contract.
    """

    name = "heuristic"
    exact = False
    wants_ticks = True

    def init_state(self, sim) -> Dict[str, np.ndarray]:
        delay = max(1, int(round(2.0 * sim.latency_s / sim.dt)))
        b, n = sim.n_rows, sim.arrays.n_nodes
        nominal = np.asarray(sim.bounds)[:, None] / \
            np.asarray(sim.n_active)[:, None]
        return {
            "buf": np.zeros((b, delay + 1, n)),
            "cap": np.repeat(nominal, n, axis=1),
        }

    @staticmethod
    def caps_fn(ctx, st, pol) -> jnp.ndarray:
        return pol["cap"]

    @staticmethod
    def tick_fn(ctx, st, pol, due):
        # The ring depth is delay + 1, so the delay is recovered from
        # the buffer shape — no extra static plumbing into the jit.
        # The row's tick index is the engine's st.tick_count (tick_fn
        # runs before the engine increments it, matching the numpy
        # heuristic's pre-increment slot / post-increment ripe check).
        depth = pol["buf"].shape[0]
        delay = depth - 1
        target, _ = reclaim_waterfill(ctx.tab, st.running, st.bound)
        slot = st.tick_count % depth
        buf = jnp.where(due, pol["buf"].at[slot].set(target), pol["buf"])
        ticks = st.tick_count + 1
        ripe = due & (ticks > delay)
        slot2 = (ticks - 1 - delay) % depth
        cap = jnp.where(ripe, buf[slot2], pol["cap"])
        return {"buf": buf, "cap": cap}
