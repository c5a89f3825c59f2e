"""Open-loop request traffic into ``SweepService.submit``.

Traffic parameters (``traffic/<name>.json``):

* ``rate_hz``: offered requests per second, fixed in the file;
* ``bound_frac``: each request's bound, a fresh fraction of its
  member's useful power range (fresh bounds: no result-cache hits);
* ``check_per_group``: requests per (member, policy) that the reference
  checks, drawn from those the window resolved.
* ``trace_seconds``: the window of a ``--trace 1`` run (its own, short
  window).

The schedule is fixed before the window: a Poisson stream conditioned
on its count, ``round(rate_hz * seconds)`` arrival times uniform over
the window, and the (member, policy) kinds in equal shares in a
shuffled order, drawn once from a constant seed.  The run's seed draws
the deployment and each request's bound.  Every seed thus offers the
same arrivals and the same sizes: when the seed also drew the arrival
order, the 90th percentile moved by a third from seed to seed and by
a few percent between two runs of one seed.  A request's latency runs
from its due time to the moment its
result resolved, so a late generator or a stalled service shows in
every later request; how late the generator submitted is recorded too.
The window ends once every request due in it has resolved, or a minute
after its close.  The service runs with its documented defaults.
"""

from __future__ import annotations

import math
import random
import time

from pb import deploy
from pb.harness import nearest_rank

#: How long after the window's close a request may still resolve.
LATE_S = 60.0


def _scenario(run, m, graph, i, bound_w, policy):
    from repro.core import Scenario

    return Scenario(name=f"{run.cfg['name']}/{m.name}", graph=graph,
                    specs=m.specs, bound_w=bound_w, policy=policy,
                    latency_s=run.cfg["latency_s"], tags={"member": i})


def setup(run):
    """Build the deployment once, start the service, and send one
    zero-work twin of each (member, policy) through it: the same bucket
    shapes as the window's, a few waves of device time."""
    from repro.serving import SweepService

    with run.span("bench.build"):
        members = deploy.build_deployment(run.cfg, run.seed)
    for m in members:
        print(f"[bench] member {m.name}: {len(m.graph.nodes)} ranks, "
              f"{len(m.graph.jobs)} jobs, digest {deploy.member_digest(m)}",
              flush=True)
    svc = SweepService(executor="jax", vector_dt=run.cfg["dt_s"],
                       shard_devices=run.chips)
    with run.span("bench.warmup"):
        tickets = [svc.submit(_scenario(run, m, m.warm_graph(), i,
                                        m.bound(0.5), p))
                   for i, m in enumerate(members)
                   for p in run.cfg["policies"]]
        svc.drain()
        bad = [t.result() for t in tickets if not t.result().ok
               or t.result().backend != "jax"]
        if bad:
            raise RuntimeError(f"warm-up: {len(bad)} requests failed, e.g. "
                               f"{bad[0].error or bad[0].backend}")
    return {"members": members, "svc": svc}


def schedule(run, n_members):
    """(due_s, member index, policy, bound fraction) per request."""
    fixed = random.Random("open_loop/arrivals")
    n = max(1, round(run.traffic["rate_hz"] * run.seconds))
    due = sorted(fixed.uniform(0.0, run.seconds) for _ in range(n))
    kinds = [(i, p) for i in range(n_members) for p in run.cfg["policies"]]
    mix = [kinds[k % len(kinds)] for k in range(n)]
    fixed.shuffle(mix)
    rng = random.Random(f"open_loop/{run.seed}")
    lo, hi = run.traffic["bound_frac"]
    return [(d, i, p, rng.uniform(lo, hi)) for d, (i, p) in zip(due, mix)]


def window(run, state):
    members, svc = state["members"], state["svc"]
    plan = schedule(run, len(members))
    scns = [_scenario(run, members[i], members[i].graph, i,
                      members[i].bound(f), p) for _, i, p, f in plan]
    print(f"[bench] window requests digest "
          f"{deploy.digest(f'{s.name} {s.policy} {s.bound_w!r}' for s in scns)}"
          f": {len(scns)}", flush=True)
    prof0 = len(svc.profile.buckets)
    phantom0 = svc.stats().phantom_rows
    tickets, submitted, late = [], [], []
    t0 = time.perf_counter()
    for (due, _, _, _), s in zip(plan, scns):
        target = t0 + due
        wait = target - time.perf_counter()
        if wait > 0:
            with run.span("bench.sleep"):
                time.sleep(wait)
        ts = time.perf_counter()
        with run.span("bench.submit"):
            tickets.append(svc.submit(s))
        submitted.append(ts)
        late.append(ts - target)
    records = []
    with run.span("bench.wait"):
        for t in tickets:
            left = t0 + run.seconds + LATE_S - time.perf_counter()
            try:
                records.append(t.result(timeout=max(left, 0.001)))
            except TimeoutError:
                records.append(None)
    latency, done = [], []
    for (due, _, _, _), ts, rec in zip(plan, submitted, records):
        if rec is None or not rec.ok:
            latency.append(math.inf)
            continue
        done.append(ts + rec.latency_s)
        latency.append(ts + rec.latency_s - (t0 + due))
    t1 = max(done, default=time.perf_counter())
    ok = [k for k, rec in enumerate(records) if rec is not None and rec.ok]
    p90 = nearest_rank(latency, 90)

    check = random.Random(f"check/{run.seed}")
    k_check = run.traffic["check_per_group"]
    groups = {}
    for k in ok:
        groups.setdefault((plan[k][1], plan[k][2]), []).append(k)
    items = []
    for (i, policy), ks in sorted(groups.items()):
        for k in check.sample(ks, min(k_check, len(ks))):
            s, res = scns[k], records[k].result
            items.append((f"req{k}:{members[i].name}/{policy}"
                          f"@{s.bound_w:.6g}W",
                          deploy.ref_scenario(members[i], s.bound_w, policy),
                          res.makespan, res.energy_j))
    buckets = svc.profile.buckets[prof0:]
    return {
        "window": (t0, t1),
        "attempted": len(scns),
        "failed": len(scns) - len(ok),
        "exact": {"unresolved": len(scns) - len(ok)},
        "e2e": {"serve_p90_s": p90 if math.isfinite(p90) else 1e9,
                "serve_done_per_s": len(ok) / (t1 - t0)},
        "items": items,
        "layer": {"requests": len(scns), "window_s": t1 - t0,
                  "late_s": late, "latency_s": latency,
                  "dispatched_rows": sum(b.rows for b in buckets),
                  "phantom_rows": svc.stats().phantom_rows - phantom0,
                  "buckets": len(buckets),
                  "cache_hits": sum(1 for k in ok if records[k].cached)},
    }


def close(state):
    state["svc"].close()
    state.clear()
