"""Fused power-redistribution wave step (Pallas + pure-jnp reference).

One wave of the batch simulators' hot path, fused into a single kernel
per scenario row:

1. **idle-power reclamation / redistribution** (optional, static flag):
   reclaim the idle draw of non-running nodes and water-fill the
   remaining cluster budget over the running ones — the steady state of
   the paper's Algorithm 1 and the oracle policy's cap rule.  ``bound``
   is a traced ``(1, 1)`` operand, so engines with dynamic bound
   schedules feed each wave the row's *current* bound and the
   reclamation/water-fill follows it with no recompilation,
2. **LUT power->frequency gather**: the §V power-to-frequency translator
   (highest DVFS state fitting each cap, sub-``p_min`` duty states
   below), expressed as an ascending compare/select scan over the
   stacked state table,
3. **per-node rate computation**: ``speed * duty / (rho * f_nom/f +
   (1 - rho))`` for running lanes,
4. **earliest-event reduction**: per-node completion times
   ``remaining / rate`` and their row minimum, plus the row's cluster
   power draw.

Shapes are per-row — lanes ``(1, N)``, LUT tables ``(S, N)``, scalars
``(1, 1)`` — so the compiled engine ``vmap``s the call over the bound
axis (Pallas' batching rule turns that into a grid dimension).  The
pure-``jnp`` reference (:func:`power_step_ref`) is bit-compatible math
and is what the engine uses by default; the Pallas kernel
(:func:`power_step`` with ``impl="pallas"``) runs in interpret mode on
CPU so CI stays green without a TPU.

Rate-less lanes get the finite sentinel :data:`BIG_TIME` instead of
``inf`` (kernel-safe min reductions); callers treat anything above
``BIG_TIME / 2`` as "no event".
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.power import DUTY_FLOOR

#: Finite stand-in for "no completion event" (kernel-safe vs inf).
BIG_TIME = 1e30


def default_interpret() -> bool:
    """Backend-detected interpret default: the Pallas interpreter on CPU
    (no Mosaic compiler there), the native compiled kernel on any real
    accelerator backend (mirrors ``repro.kernels.ops._on_tpu``).  Call
    sites pass ``interpret=None`` to get this, so GPU/TPU runs compile
    natively without per-site hardcoding."""
    return jax.default_backend() == "cpu"

#: Cap-fitting tolerance for the translator.  The numpy reference uses
#: ``1e-12`` under float64; the compiled engine runs float32, where ILP
#: caps that equal a state power exactly can round one ulp below it —
#: ``1e-6`` absorbs that and sits far under any real LUT state spacing.
FIT_ATOL = 1e-6


class StepTables(NamedTuple):
    """Per-cluster LUT constants, pre-shaped for the fused step.

    ``state_p``/``state_f`` are ``(S, N)`` — the transpose of
    :class:`~repro.core.power.LUTTable`'s layout — so the in-kernel
    gather scans the leading (state) axis; lane vectors are ``(1, N)``.
    """

    state_p: jnp.ndarray    # (S, N) full-load power, +inf padded
    state_f: jnp.ndarray    # (S, N) frequency per state
    idle_w: jnp.ndarray     # (1, N)
    f_min: jnp.ndarray      # (1, N)
    f_nom: jnp.ndarray      # (1, N)
    span: jnp.ndarray       # (1, N) p_min - idle_w
    speed: jnp.ndarray      # (1, N)
    cap_floor: jnp.ndarray  # (1, N)
    p_max: jnp.ndarray      # (1, N)


def step_tables(table, dtype=np.float32) -> StepTables:
    """Build :class:`StepTables` from a :class:`~repro.core.power.LUTTable`.

    Accepts a shared single-cluster table (``(N, S)`` state tables ->
    ``(S, N)`` / ``(1, N)`` leaves) or a per-row stacked table from
    :func:`repro.core.power.stack_lut_tables` (``(B, N, S)`` ->
    ``(B, S, N)`` / ``(B, 1, N)`` leaves, which the engine's stacked
    ``vmap`` slices back down to the kernel's per-row shapes).

    The leaves are *numpy* arrays on purpose: jitted callers convert
    them at dispatch (one fused transfer), and building them here with
    ``jnp`` would pay ~15 eager dispatches per sweep group.
    """
    lane = lambda a: np.asarray(a, dtype)[..., None, :]   # noqa: E731
    return StepTables(
        state_p=np.swapaxes(np.asarray(table.state_p, dtype), -1, -2),
        state_f=np.swapaxes(np.asarray(table.state_f, dtype), -1, -2),
        idle_w=lane(table.idle_w), f_min=lane(table.f_min),
        f_nom=lane(table.f_nom), span=lane(table.span),
        speed=lane(table.speed), cap_floor=lane(table.cap_floor),
        p_max=lane(table.p_max))


# --------------------------------------------------------------- jnp math
def translate_caps(tab: StepTables, caps: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Power-to-frequency translation: caps ``(1, N)`` -> (freq, duty,
    power), elementwise-identical to
    :func:`repro.core.power.batched_operating_point` (to float32
    precision and :data:`FIT_ATOL`).  States are scanned in ascending
    order, so the last fitting state — the highest — wins; +inf padding
    rows never fit."""
    n_states = tab.state_p.shape[0]
    freq = tab.f_min
    pfit = tab.state_p[0:1, :]
    has = jnp.zeros(caps.shape, dtype=bool)
    for s in range(n_states):
        fit = tab.state_p[s:s + 1, :] <= caps + FIT_ATOL
        freq = jnp.where(fit, tab.state_f[s:s + 1, :], freq)
        pfit = jnp.where(fit, tab.state_p[s:s + 1, :], pfit)
        has = has | fit
    q = jnp.clip((caps - tab.idle_w) / tab.span, DUTY_FLOOR, 1.0)
    freq = jnp.where(has, freq, tab.f_min)
    duty = jnp.where(has, jnp.ones_like(q), q)
    power = jnp.where(has, pfit, tab.idle_w + q * tab.span)
    return freq, duty, power


def waterfill_caps(tab: StepTables, running: jnp.ndarray,
                   budget: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Water-fill ``budget`` (``(1, 1)``) over the running lanes of one
    row: equal shares, saturated lanes clamp at ``p_max``, the surplus
    re-spreads until absorbed; non-running lanes get the cap floor.
    Row-for-row the same fixed point as
    :func:`repro.policies.vector.batched_waterfill`.

    Returns ``(caps, rounds)``: the caps ``(1, N)`` and the live rounds
    run, an int32 scalar.  A round closes every open lane whose
    ``p_max`` fits the share (the lowest levels left), or all of them
    once none does, so the loop stops when no lane is open: after at
    most as many rounds as there are distinct ``p_max`` values among
    the running lanes, never more than ``N``.  A round past that fixed
    point would change nothing.  The open mask is carried as int32: the
    TPU kernel compiler cannot carry a boolean vector through a loop."""
    n = running.shape[-1]

    def go(carry):
        _, open_, _, rounds = carry
        return jnp.any(open_ > 0) & (rounds < n)

    def body(carry):
        caps, open_, rem, rounds = carry
        open_ = open_ > 0
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
        return caps, open_.astype(jnp.int32), rem, rounds + 1

    caps = jnp.broadcast_to(tab.cap_floor, running.shape)
    caps, _, _, rounds = jax.lax.while_loop(
        go, body, (caps, running.astype(jnp.int32), budget,
                   jnp.zeros((), jnp.int32)))
    return caps, rounds


def _step_math(tab: StepTables, caps, running, remaining, rho, bound,
               redistribute: bool):
    """The fused wave: shared verbatim by the reference and the kernel
    body (the kernel only differs in how operands arrive).  The last
    output is the water-fill's live rounds, ``(1, 1)`` int32 (0 when
    ``redistribute`` is off)."""
    if redistribute:
        idle_draw = jnp.sum(jnp.where(running, 0.0, tab.idle_w), axis=-1,
                            keepdims=True)
        eff_caps, rounds = waterfill_caps(tab, running, bound - idle_draw)
    else:
        eff_caps, rounds = caps, jnp.zeros((), jnp.int32)
    freq, duty, power = translate_caps(tab, eff_caps)
    slowdown = rho * (tab.f_nom / freq) + (1.0 - rho)
    rate = jnp.where(running, tab.speed * duty / slowdown, 0.0)
    p_node = jnp.where(running, power, tab.idle_w)
    has_rate = rate > 0
    t_fin = jnp.where(has_rate,
                      remaining / jnp.where(has_rate, rate, 1.0), BIG_TIME)
    p_cluster = jnp.sum(p_node, axis=-1, keepdims=True)
    t_comp = jnp.min(t_fin, axis=-1, keepdims=True)
    return (rate, p_node, t_fin, eff_caps, p_cluster, t_comp,
            jnp.full((1, 1), rounds, jnp.int32))


def power_step_ref(tab: StepTables, caps, running, remaining, rho, bound,
                   redistribute: bool = False):
    """Pure-jnp reference: caps/running/remaining/rho ``(1, N)``, bound
    ``(1, 1)`` -> ``(rate, p_node, t_fin, eff_caps, p_cluster, t_comp,
    waterfill_rounds)`` with lane shapes ``(1, N)`` and row scalars
    ``(1, 1)`` (the rounds int32).  ``running``
    is a float mask (1.0 running / 0.0 not) for kernel parity."""
    return _step_math(tab, caps, running > 0.5, remaining, rho, bound,
                      redistribute)


# ------------------------------------------------------------ pallas kernel
def _power_step_kernel(caps_ref, running_ref, remaining_ref, rho_ref,
                       bound_ref, state_p_ref, state_f_ref, idle_ref,
                       f_min_ref, f_nom_ref, span_ref, speed_ref,
                       floor_ref, p_max_ref, rate_ref, p_node_ref,
                       t_fin_ref, eff_caps_ref, p_cluster_ref, t_comp_ref,
                       rounds_ref, *, redistribute: bool):
    tab = StepTables(
        state_p=state_p_ref[...], state_f=state_f_ref[...],
        idle_w=idle_ref[...], f_min=f_min_ref[...], f_nom=f_nom_ref[...],
        span=span_ref[...], speed=speed_ref[...],
        cap_floor=floor_ref[...], p_max=p_max_ref[...])
    rate, p_node, t_fin, eff_caps, p_cluster, t_comp, rounds = _step_math(
        tab, caps_ref[...], running_ref[...] > 0.5, remaining_ref[...],
        rho_ref[...], bound_ref[...], redistribute)
    rate_ref[...] = rate
    p_node_ref[...] = p_node
    t_fin_ref[...] = t_fin
    eff_caps_ref[...] = eff_caps
    p_cluster_ref[...] = p_cluster
    t_comp_ref[...] = t_comp
    rounds_ref[...] = rounds


def power_step_pallas(tab: StepTables, caps, running, remaining, rho,
                      bound, redistribute: bool = False,
                      interpret: bool = None):
    """Pallas form of :func:`power_step_ref` — one fused kernel per row.

    ``interpret=None`` (the default) resolves via
    :func:`default_interpret`: the Pallas interpreter on CPU (so the
    path is exercised on CPU CI), the natively compiled kernel on
    GPU/TPU.  Pass an explicit bool to force either mode.
    """
    if interpret is None:
        interpret = default_interpret()
    n = caps.shape[-1]
    dtype = caps.dtype
    lane = jax.ShapeDtypeStruct((1, n), dtype)
    scalar = jax.ShapeDtypeStruct((1, 1), dtype)
    return pl.pallas_call(
        functools.partial(_power_step_kernel, redistribute=redistribute),
        out_shape=(lane, lane, lane, lane, scalar, scalar,
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)),
        interpret=interpret,
    )(caps, running, remaining, rho, bound, tab.state_p, tab.state_f,
      tab.idle_w, tab.f_min, tab.f_nom, tab.span, tab.speed,
      tab.cap_floor, tab.p_max)


def power_step(tab: StepTables, caps, running, remaining, rho, bound,
               redistribute: bool = False, impl: str = "ref",
               interpret: bool = None):
    """Dispatch one fused wave step: ``impl`` is ``"ref"`` (pure jnp,
    the engine default) or ``"pallas"`` (fused kernel;
    ``interpret=None`` auto-resolves to the interpreter on CPU and the
    native compiled kernel off-CPU, see :func:`default_interpret`)."""
    if impl == "ref":
        return power_step_ref(tab, caps, running, remaining, rho, bound,
                              redistribute)
    if impl == "pallas":
        return power_step_pallas(tab, caps, running, remaining, rho,
                                 bound, redistribute, interpret=interpret)
    raise ValueError(f"unknown power_step impl {impl!r}")
