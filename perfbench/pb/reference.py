"""The plain reference: a discrete-event cluster simulator.

It runs one scenario (jobs on ranks, a power table per rank, a cluster
power bound and a policy) and returns its makespan and energy.  It is
written from the semantics the program documents for its event
simulator and imports nothing of the program, nor JAX: it runs in
spawned worker processes while the parent holds the chip.

Semantics, per rank: jobs run in index order; a job starts once every
job it depends on has completed; a running rank draws the power of its
operating point and progresses at ``duty * speed / (rho * f_nom / f +
1 - rho)`` work units per second; a rank that is not running draws its
idle power.  The operating point under a cap is the fastest table state
whose power fits it, or, below the slowest state, duty cycling at the
slowest state's frequency (duty floored at :data:`DUTY_FLOOR`).

Policies:

* ``equal-share``: every rank is capped at ``bound / n`` for the whole
  run;
* ``oracle``: at every change of a rank between running and not
  running, the bound less the idle draw of the ranks not running is
  water-filled over the running ranks (equal split, clamped at each
  rank's top state, the surplus spread again); ranks not running get
  the duty floor's draw.

``dtype="bfloat16"`` rounds every time, remaining work, cap and energy
the simulation keeps to bfloat16: the control that a computation one
precision below the configuration's float32 must fail.
"""

from __future__ import annotations

import heapq
import itertools
from typing import Callable, Dict, List, NamedTuple, Sequence, Tuple

#: Lowest duty cycle a capped rank runs at (the power model's floor).
DUTY_FLOOR = 0.02
#: Remaining work below this counts as done.
WORK_EPS = 1e-9

JobId = Tuple[int, int]


class Rank(NamedTuple):
    """One rank's power table and nominal speed."""

    states: Tuple[Tuple[float, float], ...]   # (freq_mhz, power_w), by freq
    idle_w: float
    speed: float


class Job(NamedTuple):
    work: float
    cpu_frac: float
    deps: Tuple[JobId, ...]


class RefScenario(NamedTuple):
    """Everything one reference run needs, as plain picklable data."""

    jobs: Tuple[Tuple[Job, ...], ...]          # per rank, in index order
    ranks: Tuple[Rank, ...]
    bound_w: float
    policy: str


def _quantizer(dtype: str) -> Callable[[float], float]:
    if dtype == "float64":
        return float
    if dtype == "bfloat16":
        import ml_dtypes
        import numpy as np

        bf16 = ml_dtypes.bfloat16
        return lambda x: float(np.asarray(x, dtype=np.float64).astype(bf16))
    raise ValueError(f"unknown reference dtype {dtype!r}")


def _cap_floor(r: Rank) -> float:
    return r.idle_w + DUTY_FLOOR * (r.states[0][1] - r.idle_w)


def _operating_point(r: Rank, cap: float) -> Tuple[float, float, float]:
    """(freq_mhz, duty, power_w) under ``cap``."""
    best = None
    for f, p in r.states:
        if p <= cap + 1e-12:
            best = (f, 1.0, p)
    if best is not None:
        return best
    p_min = r.states[0][1]
    span = p_min - r.idle_w
    duty = min(1.0, max(DUTY_FLOOR, (cap - r.idle_w) / span))
    return (r.states[0][0], duty, r.idle_w + duty * span)


def _rate(r: Rank, op, job: Job) -> float:
    f, duty, _ = op
    if job.work <= 0:
        return float("inf")
    f_nom = r.states[-1][0]
    rho = job.cpu_frac
    return duty * r.speed / (rho * (f_nom / f) + (1.0 - rho))


def _waterfill(ranks: Sequence[Rank], running: List[int],
               budget: float) -> Dict[int, float]:
    caps: Dict[int, float] = {}
    open_set = list(running)
    left = budget
    while open_set:
        share = left / len(open_set)
        full = [i for i in open_set if ranks[i].states[-1][1] <= share + 1e-12]
        if not full:
            for i in open_set:
                caps[i] = min(max(share, _cap_floor(ranks[i])),
                              ranks[i].states[-1][1])
            break
        for i in full:
            caps[i] = ranks[i].states[-1][1]
            left -= caps[i]
            open_set.remove(i)
    return caps


def simulate(scn: RefScenario, dtype: str = "float64") -> Dict[str, float]:
    """Run one scenario; returns ``{"makespan": s, "energy": J}``."""
    q = _quantizer(dtype)
    ranks, jobs = scn.ranks, scn.jobs
    n = len(ranks)
    if scn.policy not in ("equal-share", "oracle"):
        raise ValueError(f"no reference for policy {scn.policy!r}")
    oracle = scn.policy == "oracle"
    bound = q(scn.bound_w)
    total = sum(len(js) for js in jobs)

    ptr = [0] * n
    state = ["blocked"] * n                   # running | blocked | done
    cap = [q(bound / n)] * n
    op = [_operating_point(ranks[i], cap[i]) for i in range(n)]
    remaining = [0.0] * n
    last_update = [0.0] * n
    version = [0] * n
    completed: set = set()
    waiters: Dict[JobId, List[int]] = {}
    ends: List[float] = []
    running_view = [True] * n                 # the oracle's view
    heap: List[tuple] = []
    seq = itertools.count()
    acc = {"energy": 0.0, "t": 0.0, "p": 0.0}

    def power() -> float:
        return sum(op[i][2] if state[i] == "running" else ranks[i].idle_w
                   for i in range(n))

    def account(t: float) -> None:
        dt = t - acc["t"]
        if dt > 0:
            acc["energy"] = q(acc["energy"] + acc["p"] * dt)
        acc["t"] = t
        acc["p"] = power()

    def progress(i: int, t: float) -> None:
        job = jobs[i][ptr[i]] if ptr[i] < len(jobs[i]) else None
        if state[i] == "running" and job is not None and job.work > 0:
            remaining[i] = q(max(0.0, remaining[i] - _rate(ranks[i], op[i], job)
                                 * (t - last_update[i])))
        last_update[i] = t

    def reschedule(i: int, t: float) -> None:
        if state[i] != "running" or ptr[i] >= len(jobs[i]):
            return
        version[i] += 1
        rate = _rate(ranks[i], op[i], jobs[i][ptr[i]])
        dur = remaining[i] / rate if rate > 0 else 0.0
        t_fin = q(t + dur)
        if t_fin <= t:
            # The time left rounds away in this precision: done now.
            remaining[i] = 0.0
            t_fin = t
        heapq.heappush(heap, (t_fin, next(seq), i, version[i]))

    def set_cap(i: int, c: float, t: float) -> None:
        progress(i, t)
        cap[i] = q(c)
        new = _operating_point(ranks[i], cap[i])
        if new != op[i]:
            op[i] = new
            reschedule(i, t)

    def resolve(t: float) -> None:
        if not oracle:
            return
        run = [i for i in range(n) if running_view[i]]
        idle = sum(ranks[i].idle_w for i in range(n) if not running_view[i])
        caps = _waterfill(ranks, run, q(bound - idle))
        for i in range(n):
            c = caps.get(i)
            if c is None:
                c = min(max(0.0, _cap_floor(ranks[i])), ranks[i].states[-1][1])
            if abs(cap[i] - c) > 1e-9:
                set_cap(i, c, t)

    def report(i: int, running: bool, t: float) -> None:
        running_view[i] = running
        resolve(t)

    def deps_ready(job: Job) -> bool:
        return all(d in completed for d in job.deps)

    def advance(i: int, t: float) -> None:
        if ptr[i] >= len(jobs[i]):
            if state[i] != "done":
                state[i] = "done"
                report(i, False, t)
            return
        job = jobs[i][ptr[i]]
        if deps_ready(job):
            was_blocked = state[i] == "blocked"
            state[i] = "running"
            remaining[i] = q(job.work)
            last_update[i] = t
            reschedule(i, t)
            if was_blocked:
                report(i, True, t)
        else:
            for d in job.deps:
                if d not in completed:
                    waiters.setdefault(d, []).append(i)
            state[i] = "blocked"
            report(i, False, t)

    account(0.0)
    for i in range(n):
        advance(i, 0.0)
    account(0.0)
    while heap:
        t, _, i, ver = heapq.heappop(heap)
        if ver != version[i] or state[i] != "running":
            continue
        progress(i, t)
        if remaining[i] > WORK_EPS:
            reschedule(i, t)
            continue
        account(t)
        jid = (i, ptr[i])
        completed.add(jid)
        ends.append(t)
        ptr[i] += 1
        advance(i, t)
        for w in waiters.pop(jid, []):
            if state[w] == "blocked" and ptr[w] < len(jobs[w]) \
                    and deps_ready(jobs[w][ptr[w]]):
                advance(w, t)
        account(t)
        if len(completed) == total:
            break
    if len(completed) != total:
        raise RuntimeError(f"deadlock: {total - len(completed)} jobs never ran")
    makespan = max(ends, default=0.0)
    account(makespan)
    return {"makespan": makespan, "energy": acc["energy"]}
