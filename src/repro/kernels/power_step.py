"""The power-redistribution wave step, in plain ``jnp``.

One wave of the compiled stepper's hot path
(:mod:`repro.backends.jax.engine`), for one scenario row:

1. **idle-power reclamation / redistribution** (optional, static flag):
   reclaim the idle draw of non-running nodes and water-fill the
   remaining cluster budget over the running ones — the steady state of
   the paper's Algorithm 1 and the oracle policy's cap rule.  ``bound``
   is a traced scalar, so engines with dynamic bound schedules feed
   each wave the row's *current* bound and the reclamation/water-fill
   follows it with no recompilation,
2. **LUT power->frequency gather**: the §V power-to-frequency translator
   (highest DVFS state fitting each cap, sub-``p_min`` duty states
   below), expressed as an ascending compare/select scan over the
   stacked state table,
3. **per-node rate computation**: ``speed * duty / (rho * f_nom/f +
   (1 - rho))`` for running lanes,
4. **earliest-event reduction**: per-node completion times
   ``remaining / rate`` and their row minimum, plus the row's cluster
   power draw.

Shapes are the stepper's own, per row: lanes ``(N,)``, LUT state
tables ``(S, N)``, scalars ``()``.  The engine ``vmap``s the stepper,
and with it this step, over the row axis.

Rate-less lanes get the finite sentinel :data:`BIG_TIME` instead of
``inf`` (finite min reductions); callers treat anything above
``BIG_TIME / 2`` as "no event".
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.power import DUTY_FLOOR

#: Finite stand-in for "no completion event" (finite vs inf).
BIG_TIME = 1e30

#: Cap-fitting tolerance for the translator.  The numpy reference uses
#: ``1e-12`` under float64; the compiled engine runs float32, where ILP
#: caps that equal a state power exactly can round one ulp below it —
#: ``1e-6`` absorbs that and sits far under any real LUT state spacing.
FIT_ATOL = 1e-6


class StepTables(NamedTuple):
    """Per-cluster LUT constants, pre-shaped for the wave step.

    ``state_p``/``state_f`` are ``(S, N)`` — the transpose of
    :class:`~repro.core.power.LUTTable`'s layout — so the translation
    scans the leading (state) axis; lane vectors are ``(N,)``.
    """

    state_p: jnp.ndarray    # (S, N) full-load power, +inf padded
    state_f: jnp.ndarray    # (S, N) frequency per state
    idle_w: jnp.ndarray     # (N,)
    f_min: jnp.ndarray      # (N,)
    f_nom: jnp.ndarray      # (N,)
    span: jnp.ndarray       # (N,) p_min - idle_w
    speed: jnp.ndarray      # (N,)
    cap_floor: jnp.ndarray  # (N,)
    p_max: jnp.ndarray      # (N,)


def step_tables(table, dtype=np.float32) -> StepTables:
    """Build :class:`StepTables` from a :class:`~repro.core.power.LUTTable`.

    Accepts a shared single-cluster table (``(N, S)`` state tables ->
    ``(S, N)`` / ``(N,)`` leaves) or a per-row stacked table from
    :func:`repro.core.power.stack_lut_tables` (``(B, N, S)`` ->
    ``(B, S, N)`` / ``(B, N)`` leaves, which the engine's stacked
    ``vmap`` slices back down to the per-row shapes).

    The leaves are *numpy* arrays on purpose: jitted callers convert
    them at dispatch (one fused transfer), and building them here with
    ``jnp`` would pay ~15 eager dispatches per sweep group.
    """
    lane = lambda a: np.asarray(a, dtype)   # noqa: E731
    return StepTables(
        state_p=np.swapaxes(np.asarray(table.state_p, dtype), -1, -2),
        state_f=np.swapaxes(np.asarray(table.state_f, dtype), -1, -2),
        idle_w=lane(table.idle_w), f_min=lane(table.f_min),
        f_nom=lane(table.f_nom), span=lane(table.span),
        speed=lane(table.speed), cap_floor=lane(table.cap_floor),
        p_max=lane(table.p_max))


def translate_caps(tab: StepTables, caps: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Power-to-frequency translation: caps ``(N,)`` -> (freq, duty,
    power), elementwise-identical to
    :func:`repro.core.power.batched_operating_point` (to float32
    precision and :data:`FIT_ATOL`).  States are scanned in ascending
    order, so the last fitting state — the highest — wins; +inf padding
    rows never fit."""
    n_states = tab.state_p.shape[0]
    freq = tab.f_min
    pfit = tab.state_p[0]
    has = jnp.zeros(caps.shape, dtype=bool)
    for s in range(n_states):
        fit = tab.state_p[s] <= caps + FIT_ATOL
        freq = jnp.where(fit, tab.state_f[s], freq)
        pfit = jnp.where(fit, tab.state_p[s], pfit)
        has = has | fit
    q = jnp.clip((caps - tab.idle_w) / tab.span, DUTY_FLOOR, 1.0)
    freq = jnp.where(has, freq, tab.f_min)
    duty = jnp.where(has, jnp.ones_like(q), q)
    power = jnp.where(has, pfit, tab.idle_w + q * tab.span)
    return freq, duty, power


def waterfill_caps(tab: StepTables, running: jnp.ndarray,
                   budget: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Water-fill the scalar ``budget`` over the running lanes of one
    row: equal shares, saturated lanes clamp at ``p_max``, the surplus
    re-spreads until absorbed; non-running lanes get the cap floor.
    Row-for-row the same fixed point as
    :func:`repro.policies.vector.batched_waterfill`.

    Returns ``(caps, rounds)``: the caps ``(N,)`` and the live rounds
    run, an int32 scalar.  A round closes every open lane whose
    ``p_max`` fits the share (the lowest levels left), or all of them
    once none does, so the loop stops when no lane is open: after at
    most as many rounds as there are distinct ``p_max`` values among
    the running lanes, never more than ``N``.  A round past that fixed
    point would change nothing."""
    n = running.shape[-1]

    def go(carry):
        _, open_, _, rounds = carry
        return jnp.any(open_) & (rounds < n)

    def body(carry):
        caps, open_, rem, rounds = carry
        n_open = jnp.sum(open_)
        live = n_open > 0
        share = jnp.where(live, rem / jnp.maximum(n_open, 1), 0.0)
        sat = open_ & (tab.p_max <= share + FIT_ATOL)
        finished = live & ~jnp.any(sat)
        caps = jnp.where(open_ & finished,
                         jnp.clip(share, tab.cap_floor, tab.p_max), caps)
        caps = jnp.where(sat, tab.p_max, caps)
        rem = rem - jnp.sum(jnp.where(sat, tab.p_max, 0.0))
        open_ = open_ & ~sat & ~finished
        return caps, open_, rem, rounds + 1

    caps = jnp.broadcast_to(tab.cap_floor, running.shape)
    caps, _, _, rounds = jax.lax.while_loop(
        go, body, (caps, running, budget, jnp.zeros((), jnp.int32)))
    return caps, rounds


def reclaim_waterfill(tab: StepTables, running: jnp.ndarray,
                      bound: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The oracle's cap rule: reclaim the idle draw of the non-running
    lanes from the scalar ``bound`` and water-fill the rest over the
    running ones (:func:`waterfill_caps`'s ``(caps, rounds)``)."""
    idle_draw = jnp.sum(jnp.where(running, 0.0, tab.idle_w))
    return waterfill_caps(tab, running, bound - idle_draw)


def power_step(tab: StepTables, caps, running, remaining, rho, bound,
               redistribute: bool):
    """One wave of one row: ``caps``, ``remaining``, ``rho`` ``(N,)``,
    ``running`` a bool ``(N,)`` and ``bound`` a scalar -> ``(rate,
    t_fin, p_cluster, t_comp, waterfill_rounds)``, the lanes ``(N,)``
    and the rest scalars.  With ``redistribute`` the water-fill of the
    reclaimed ``bound`` replaces ``caps``; ``waterfill_rounds`` is its
    live rounds, an int32 (0 when ``redistribute`` is off)."""
    if redistribute:
        caps, rounds = reclaim_waterfill(tab, running, bound)
    else:
        rounds = jnp.zeros((), jnp.int32)
    freq, duty, power = translate_caps(tab, caps)
    slowdown = rho * (tab.f_nom / freq) + (1.0 - rho)
    rate = jnp.where(running, tab.speed * duty / slowdown, 0.0)
    p_node = jnp.where(running, power, tab.idle_w)
    has_rate = rate > 0
    t_fin = jnp.where(has_rate,
                      remaining / jnp.where(has_rate, rate, 1.0), BIG_TIME)
    return rate, t_fin, jnp.sum(p_node), jnp.min(t_fin), rounds
