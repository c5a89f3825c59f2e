"""Trainer for the ``"learned"`` cap policy (gradient through the soft
simulator).

Loss: mean over a rho-diverse scenario set of ``soft makespan /
equal-share exact makespan`` — the normalization puts every scenario on
the same scale (1.0 = "no better than the paper's baseline") so no
single large graph dominates the gradient.  The parameters are the MLP
of :mod:`repro.policies.learned`; gradients flow through
:func:`repro.diff.softsim.soft_makespan_policy`, which calls the exact
same ``compute_caps`` the event/vector/jax adapters run, so the result
IS the deployed policy.

With the zero output layer the initial policy is already equal-split
reclamation; what training adds is lane *discrimination* — features
only distinguish lanes by ``running`` and the current job's
``cpu_frac``, so rho-diverse workloads (``layered_dag``) carry the
signal and rho-homogeneous ones (``listing2``) anchor the symmetric
baseline behaviour.

Run as a script to (re)produce the bundled checkpoint::

    PYTHONPATH=src python -m repro.diff.train --steps 150 \\
        --out src/repro/policies/learned_default.json
"""

from __future__ import annotations

import argparse
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends.jax import enable_compile_cache
from repro.core.power import (NodeSpec, homogeneous_cluster,
                              min_feasible_cluster_bound,
                              max_useful_cluster_bound)
from repro.core.workloads import fork_join_graph, layered_dag, listing2_graph
from repro.policies.learned import init_params, save_checkpoint

from .softsim import build_soft_arrays, soft_makespan_policy


def training_scenarios(seed: int = 0, quick: bool = False
                       ) -> List[Tuple[str, object, Sequence[NodeSpec],
                                       float]]:
    """(name, graph, specs, bound) tuples: layered DAGs across seeds and
    bound tightnesses (the rho-diverse signal), fork-join barriers, and
    listing2 (the symmetric anchor)."""
    out = []
    fracs = (0.35, 0.55) if quick else (0.3, 0.45, 0.6)
    seeds = (seed + 1, seed + 2) if quick else (seed + 1, seed + 2,
                                                seed + 3)
    for s in seeds:
        for n in (4,) if quick else (4, 6):
            g = layered_dag(n, layers=3, fan=2, seed=s)
            specs = homogeneous_cluster(n)
            lo = min_feasible_cluster_bound(specs)
            hi = max_useful_cluster_bound(specs)
            for f in fracs:
                out.append((f"layered-n{n}-s{s}-f{f}", g, specs,
                            lo + f * (hi - lo)))
    g = fork_join_graph(4, stages=2, seed=seed + 9)
    specs = homogeneous_cluster(4)
    lo, hi = (min_feasible_cluster_bound(specs),
              max_useful_cluster_bound(specs))
    out.append(("forkjoin-4", g, specs, lo + 0.4 * (hi - lo)))
    g = listing2_graph()
    specs = homogeneous_cluster(3)
    out.append(("listing2", g, specs, 9.0))
    return out


def train_policy(seed: int = 0, steps: int = 150, lr: float = 0.02,
                 temperatures: Sequence[float] = (0.3, 0.1, 0.05),
                 quick: bool = False, verbose: bool = True
                 ) -> Tuple[Dict[str, np.ndarray], dict]:
    """Adam over the scenario-mean normalized soft makespan.

    Returns ``(params, meta)``; ``meta`` records the scenario list and
    the per-phase loss trajectory (1.0 = equal-share parity).
    """
    from repro.core.batchsim import simulate_batch

    scenarios = training_scenarios(seed, quick=quick)
    params = {k: jnp.asarray(v) for k, v in init_params(seed).items()}

    grads_fns = []
    for name, g, specs, bound in scenarios:
        soft = build_soft_arrays(g, specs)
        base = simulate_batch(g, specs, [bound],
                              policy="equal-share")[0].makespan

        def obj(params, temp, soft=soft, bound=bound, base=base):
            return soft_makespan_policy(params, soft, bound, temp) / base

        grads_fns.append((name, jax.jit(jax.value_and_grad(obj))))

    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    history: List[Tuple[int, float, float]] = []
    per_temp = max(1, steps // len(temperatures))
    step = 0
    for temp in temperatures:
        for _ in range(per_temp):
            step += 1
            total = 0.0
            gsum = jax.tree.map(jnp.zeros_like, params)
            for _, fn in grads_fns:
                val, g = fn(params, temp)
                total += float(val)
                gsum = jax.tree.map(jnp.add, gsum, g)
            k = len(grads_fns)
            gmean = jax.tree.map(lambda x: x / k, gsum)
            m = jax.tree.map(lambda a, b_: b1 * a + (1 - b1) * b_, m, gmean)
            v = jax.tree.map(lambda a, b_: b2 * a + (1 - b2) * b_ * b_,
                             v, gmean)
            t_ = step
            params = jax.tree.map(
                lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** t_))
                / (jnp.sqrt(v_ / (1 - b2 ** t_)) + eps), params, m, v)
        history.append((step, float(temp), total / k))
        if verbose:
            print(f"step {step:4d}  T={temp:<5}  "
                  f"loss={total / k:.5f} (1.0 = equal-share)")

    params_np = {k: np.asarray(v, dtype=float) for k, v in params.items()}
    meta = {
        "seed": seed, "steps": step, "lr": lr,
        "temperatures": list(map(float, temperatures)),
        "scenarios": [name for name, *_ in scenarios],
        "loss_history": [[s, t, l] for s, t, l in history],
    }
    return params_np, meta


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--quick", action="store_true",
                    help="smaller scenario set (CI smoke)")
    ap.add_argument("--out", default=None,
                    help="checkpoint path (default: print only)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    params, meta = train_policy(seed=args.seed, steps=args.steps,
                                lr=args.lr, quick=args.quick)
    if args.out:
        save_checkpoint(params, args.out, meta=meta)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
