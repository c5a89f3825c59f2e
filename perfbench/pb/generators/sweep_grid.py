"""Offline sweep traffic: whole ``SweepEngine(executor="jax").run``
calls over the configuration's grid, one pass per call.

Traffic parameters (``traffic/<name>.json``):

* ``bounds_per_group``: bounds per (member, policy) in each pass, drawn
  fresh for every pass, uniformly in ``bound_frac`` of each member's
  useful power range;
* ``check_per_group``: scenarios per (member, policy) that the
  reference checks, drawn from those the window resolved.
* ``trace_seconds``: the window of a ``--trace 1`` run (its own, short
  window: the first pass that ends after it).

The window runs whole passes, so every run does whole passes of the
same mix, and starts another only while it would end within
``--seconds`` by the mean pass so far; it runs one at least.
``sweep_scen_per_s`` is every scenario resolved over the whole window.
"""

from __future__ import annotations

import random
import sys
import time

from pb import deploy


def _grid(run, members, fracs):
    from repro.core import Scenario

    cfg = run.cfg
    return [Scenario(name=f"{cfg['name']}/{m.name}", graph=graph,
                     specs=m.specs, bound_w=m.bound(f), policy=policy,
                     latency_s=cfg["latency_s"], tags={"member": i})
            for i, (m, graph) in enumerate(members)
            for policy, fs in zip(cfg["policies"], fracs[i])
            for f in fs]


def _fracs(run, rng, n_members):
    lo, hi = run.traffic["bound_frac"]
    k = run.traffic["bounds_per_group"]
    return [[[rng.uniform(lo, hi) for _ in range(k)]
             for _ in run.cfg["policies"]] for _ in range(n_members)]


def _check_sweep(sweep, what):
    bad = [r for r in sweep if not r.ok or r.backend != "jax"]
    if bad:
        raise RuntimeError(f"{what}: {len(bad)} scenarios failed or left "
                           f"jax, e.g. {bad[0].error or bad[0].backend}")


def setup(run):
    """Build the deployment once and compile every bucket of the grid on
    its zero-work twin (same shapes, a few waves of device time)."""
    from repro.core import SweepEngine

    with run.span("bench.build"):
        members = deploy.build_deployment(run.cfg, run.seed)
    for m in members:
        print(f"[bench] member {m.name}: {len(m.graph.nodes)} ranks, "
              f"{len(m.graph.jobs)} jobs, digest {deploy.member_digest(m)}",
              flush=True)
    engine = SweepEngine(executor="jax", vector_dt=run.cfg["dt_s"],
                         shard_devices=run.chips)
    warm = [(m, m.warm_graph()) for m in members]
    with run.span("bench.warmup"):
        fracs = _fracs(run, random.Random(0), len(members))
        _check_sweep(engine.run(_grid(run, warm, fracs)), "warm-up")
    return {"members": members, "engine": engine}


def window(run, state):
    members, engine = state["members"], state["engine"]
    pairs = [(m, m.graph) for m in members]
    rng = random.Random(f"sweep_grid/{run.seed}")
    records, buckets = [], []
    t0 = t1 = time.perf_counter()
    while True:
        with run.span("bench.grid"):
            grid = _grid(run, pairs, _fracs(run, rng, len(members)))
        with run.span("bench.sweep_run"):
            sweep = engine.run(grid)
        records.extend(sweep)
        buckets.extend(sweep.profile.buckets)
        t_pass, t1 = t1, time.perf_counter()
        passes = len(records) // len(grid)
        print(f"[bench] pass {passes}: {len(grid)} scenarios in "
              f"{t1 - t_pass:.3f} s", file=sys.stderr, flush=True)
        if (t1 - t0) * (passes + 1) / passes > run.seconds:
            break
    ok = [r for r in records if r.ok and r.backend == "jax"]
    cells = [f"{r.scenario.name} {r.scenario.policy} {r.scenario.bound_w!r}"
             for r in records]
    print(f"[bench] window scenarios digest {deploy.digest(cells)}: "
          f"{len(records) // len(grid)} passes of {len(grid)}", flush=True)

    check = random.Random(f"check/{run.seed}")
    k = run.traffic["check_per_group"]
    groups = {}
    for r in ok:
        groups.setdefault((r.scenario.tags["member"], r.scenario.policy),
                          []).append(r)
    items = []
    for (i, policy), rs in sorted(groups.items()):
        for r in check.sample(rs, min(k, len(rs))):
            s = r.scenario
            items.append((f"{members[i].name}/{policy}@{s.bound_w:.6g}W",
                          deploy.ref_scenario(members[i], s.bound_w, policy),
                          r.result.makespan, r.result.energy_j))
    narrow = sum(1 for b in buckets if b.devices < min(run.chips, b.rows))
    return {
        "window": (t0, t1),
        "attempted": len(records),
        "failed": len(records) - len(ok),
        "exact": {"unresolved": len(records) - len(ok),
                  "narrow_buckets": narrow},
        "e2e": {"sweep_scen_per_s": len(ok) / (t1 - t0)},
        "items": items,
        "layer": {"scenarios": len(ok), "window_s": t1 - t0,
                  "pack_s": sum(b.pack_s for b in buckets),
                  "buckets": len(buckets)},
    }


def close(state):
    state.clear()
