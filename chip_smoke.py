#!/usr/bin/env python3
"""Run the compiled power-redistribution path once on a TPU.

    python chip_smoke.py              # one chip: phases a-f
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

Phases, in one process (a chip belongs to one process at a time):

a. device check: prints what JAX found; exits 1 unless it is a TPU.
   There is no CPU fallback.
b. ``SweepEngine(executor="jax")`` over NPB class B (IS, EP, CG) at 64
   and 256 ranks, then over the ~1.1k-cell family grid of
   ``benchmarks.family_sweep``; each once cold and once warm.  The
   warm run must compile nothing.
c. the plain reference: the event simulator (``core/simulator.py``), in
   spawned worker processes that never load JAX, against the jax
   results of the quick mixed family and of one bound of every NPB
   member at 64 ranks and of IS at 256 ranks.
d. the Pallas ``power_step`` kernel compiled natively on one oracle
   NPB-IS bucket at 256 ranks, against the same bucket on the jnp
   reference.
e. ``repro.launch.serve`` over ``examples/traces`` and the
   ``repro.cluster`` replay of ``examples/cluster/arrivals_1k.jsonl``,
   both with ``--expect-clean``.
f. a few gradient steps of ``repro.diff.train`` in float32.

With ``--chips 4`` only the sharded path runs: the phase-b NPB grid
(at four bounds, so that each bucket has a row per chip, and without
CG at 256 ranks) with ``shard_devices=4`` against ``shard_devices=1``.

Each phase prints one line: its counts, ``compile_s`` (the tracing,
lowering and compiling JAX reports through its monitoring events),
``run_s`` (the rest of the phase's wall time) and the persistent
compile-cache hits.  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed phase prints its traceback to standard error and the script
exits 1 without that line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SEED = 0
#: The batched backends' control tick, and the differential envelope
#: the test suite holds them to (tests/test_batchsim_diff.py).
DT = 0.05
MAKESPAN_ATOL = 2 * DT
ENERGY_RTOL = 0.01
#: The tick-quantized heuristic's looser makespan envelope (same file).
HEURISTIC_RTOL = 0.10

NPB_NODES = (64, 256)
NPB_POLICIES = ("equal-share", "oracle")
NPB_FRACS = (0.3, 0.6)
#: Four bounds per member: the engine never shards a bucket wider than
#: its row count, and each NPB bucket holds one member.
SHARD_FRACS = (0.3, 0.45, 0.6, 0.75)
SHARD_CHIPS = 4

CORPUS = ROOT / "examples" / "traces"
ARRIVALS = ROOT / "examples" / "cluster" / "arrivals_1k.jsonl"
CLUSTER_NODES = 12


class SmokeError(RuntimeError):
    """A phase's own check failed."""


def check(ok: bool, message: str) -> None:
    """Raise :class:`SmokeError` with ``message`` unless ``ok``."""
    if not ok:
        raise SmokeError(message)


class Clock:
    """Per-phase compile seconds and cache hits, from JAX's monitoring
    events, and the one line each phase prints.

    Compile seconds are the union of the intervals in which JAX traced,
    lowered or compiled: tracing an outer jit also traces the jits it
    calls, so a plain sum of the events would count that time twice.
    """

    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._spans: list = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in self.COMPILE_EVENTS:
            end = time.perf_counter()
            with self._lock:
                self._spans.append((end - duration, end))

    def _on_event(self, event: str, **_) -> None:
        if event == self.CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def compile_seconds(self, t0: float, t1: float) -> float:
        """Wall seconds within ``[t0, t1]`` in which JAX was compiling."""
        with self._lock:
            spans = sorted((max(a, t0), min(b, t1)) for a, b in self._spans
                           if b > t0 and a < t1)
        total, reach = 0.0, t0
        for a, b in spans:
            if b > reach:
                total += b - max(a, reach)
                reach = b
        return total

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase; the body fills the yielded dict with counts."""
        fields: dict = {}
        h0, t0 = self.cache_hits, time.perf_counter()
        yield fields
        t1 = time.perf_counter()
        comp = self.compile_seconds(t0, t1)
        counts = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"[smoke] {name}: {counts} compile_s={comp:.3f} "
              f"run_s={t1 - t0 - comp:.3f} "
              f"cache_hits={self.cache_hits - h0}", flush=True)


# ----------------------------------------------------------------- inputs
def npb_scenarios(nodes=NPB_NODES, bound_fracs=NPB_FRACS, seed=SEED):
    """NPB class-B IS/EP/CG members at ``nodes`` ranks."""
    from repro.core import npb_family

    return npb_family(seed, klass="B", nodes=nodes, policies=NPB_POLICIES,
                      bound_fracs=bound_fracs).scenarios()


def family_grid(quick: bool, seed=SEED):
    """The family bench's grid: ~1.1k cells, or the quick mixed family."""
    from benchmarks.family_sweep import build_family_scenarios

    return build_family_scenarios(quick=quick, seed=seed)


def n_ranks(s) -> int:
    return len(s.graph.nodes)


# ----------------------------------------------------------------- phases
def device_check(clock: Clock, chips: int) -> dict:
    """Phase a: the device JAX reports, or :class:`SmokeError`."""
    import jax

    with clock.phase("a.device") as f:
        devices = jax.devices()
        dev = devices[0]
        print(f"[smoke] a.device: jax {jax.__version__} devices={devices}",
              flush=True)
        f.update(platform=dev.platform, kind=repr(dev.device_kind),
                 count=len(devices))
        check(dev.platform == "tpu",
              f"no TPU: JAX found platform {dev.platform!r}; this smoke "
              f"runs on the chip only and has no CPU fallback")
        check(len(devices) >= chips,
              f"{chips} chips asked for, JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def check_sweep(label: str, sweep) -> None:
    """No failure, every record on jax, no event fallback."""
    bad = sweep.failures
    check(not bad, f"{label}: {len(bad)} failed cells, e.g. "
                   f"{[(r.scenario.name, r.error) for r in bad[:3]]}")
    off = [r for r in sweep if r.backend != "jax"]
    check(not off, f"{label}: {len(off)} records not on jax, e.g. "
                   f"{[(r.scenario.name, r.backend, r.fallback_reason) for r in off[:3]]}")
    check(not sweep.event_fallbacks(),
          f"{label}: {len(sweep.event_fallbacks())} event fallbacks")


def sweep_phase(clock: Clock, label: str, scenarios, *, warm: bool = True,
                **engine_kw):
    """Phase b: one jax sweep cold, then (``warm``) again; the warm run
    must compile nothing.  ``engine_kw`` goes to the ``SweepEngine``.
    Returns the last :class:`SweepResult`."""
    from repro.backends.jax import stepper_cache_size
    from repro.core import SweepEngine

    engine = SweepEngine(executor="jax", vector_dt=DT, **engine_kw)
    for run in ("cold", "warm") if warm else ("cold",):
        with clock.phase(f"b.{label}.{run}") as f:
            size0 = stepper_cache_size()
            sweep = engine.run(scenarios)
            grown = stepper_cache_size() - size0
            check_sweep(f"{label}/{run}", sweep)
            prof = sweep.profile
            f.update(cells=len(sweep), buckets=len(prof.buckets),
                     compiled=prof.compiles, cached=prof.cache_hits,
                     jit_cache_growth=grown,
                     max_ranks=max(map(n_ranks, scenarios)),
                     max_jobs=max(len(s.graph.jobs) for s in scenarios),
                     failures=0, non_jax=0, event_fallbacks=0,
                     device_run_s=round(prof.total("run"), 3))
            if run == "warm":
                check(prof.compiles == 0 and grown == 0,
                      f"{label}: warm run compiled {prof.compiles} "
                      f"buckets (jit cache grew by {grown})")
    return sweep


def _event_reference(s):
    """One event-simulator run in a pool worker, and whether that worker
    has JAX loaded at all (it must not: the chip is the parent's)."""
    from repro.core import simulate

    result = simulate(s.graph, list(s.specs), s.bound_w, policy=s.policy,
                      latency_s=s.latency_s, trace_every=None,
                      bound_schedule=s.bound_schedule)
    return result, "jax" in sys.modules


def reference_phase(clock: Clock, pairs, max_workers=None) -> dict:
    """Phase c: ``pairs`` of (scenario, jax SimResult) against the event
    simulator, run in a spawned process pool."""
    from repro.core import SweepEngine
    from repro.policies import get_vector_policy

    # Longest first: the pool then finishes near the longest single run.
    pairs = sorted(pairs, key=lambda p: -len(p[0].graph.jobs))
    if max_workers is None:
        max_workers = min(len(pairs), len(os.sched_getaffinity(0)))
    with clock.phase("c.reference") as f:
        recs = SweepEngine(executor="process", max_workers=max_workers).map(
            _event_reference, [s for s, _ in pairs],
            label=lambda s: f"{s.name}/{s.policy_key}@{s.bound_w:.4g}W")
        bad = [r for r in recs if not r.ok]
        check(not bad, f"event reference failed: "
                       f"{[(r.label, r.error) for r in bad[:3]]}")
        loaded = [r.label for r in recs if r.value[1]]
        check(not loaded, f"pool workers loaded JAX: {loaded[:3]}")
        worst = {"dmakespan_s": 0.0, "denergy_rel": 0.0,
                 "dmakespan_s_256": 0.0, "denergy_rel_256": 0.0,
                 "heuristic_rel": 0.0}
        violations = []
        n_exact = 0
        for (s, got), rec in zip(pairs, recs):
            ev = rec.value[0]
            dm = abs(got.makespan - ev.makespan)
            de = abs(got.energy_j - ev.energy_j) / max(ev.energy_j, 1e-12)
            if get_vector_policy(s.policy).exact:
                n_exact += 1
                worst["dmakespan_s"] = max(worst["dmakespan_s"], dm)
                worst["denergy_rel"] = max(worst["denergy_rel"], de)
                if n_ranks(s) >= max(NPB_NODES):
                    worst["dmakespan_s_256"] = max(
                        worst["dmakespan_s_256"], dm)
                    worst["denergy_rel_256"] = max(
                        worst["denergy_rel_256"], de)
                if dm > MAKESPAN_ATOL or de > ENERGY_RTOL:
                    violations.append((rec.label, ev.makespan,
                                       got.makespan, de))
            else:
                rel = dm / max(ev.makespan, 1e-12)
                worst["heuristic_rel"] = max(worst["heuristic_rel"], rel)
                if rel > HEURISTIC_RTOL:
                    violations.append((rec.label, ev.makespan,
                                       got.makespan, de))
        f.update(cells=len(pairs), exact=n_exact,
                 tick_quantized=len(pairs) - n_exact,
                 max_ranks=max(n_ranks(s) for s, _ in pairs),
                 jax_in_workers=0,
                 **{f"max_{k}": f"{v:.6g}" for k, v in worst.items()})
        check(not violations,
              f"{len(violations)} cells outside the envelope "
              f"(|dmakespan| <= {MAKESPAN_ATOL} s, |denergy| <= "
              f"{ENERGY_RTOL:.0%}; heuristic {HEURISTIC_RTOL:.0%}): "
              f"(cell, event makespan, jax makespan, denergy) "
              f"{violations[:5]}")
    return worst


def reference_pairs(quick_sweep, npb_sweep):
    """The phase-c subset: the quick mixed family in full, plus the
    first bound of every NPB member at the fewest ranks and of IS at
    the most (64 and 256 in the full run)."""
    first = {}
    for r in npb_sweep:
        s = r.scenario
        first[s.name] = min(first.get(s.name, math.inf), s.bound_w)
    ranks = [n_ranks(r.scenario) for r in npb_sweep]
    pairs = [(r.scenario, r.result) for r in quick_sweep]
    for r in npb_sweep:
        s = r.scenario
        if s.bound_w != first[s.name]:
            continue
        if n_ranks(s) == min(ranks) or (s.tags.get("kind") == "is"
                                        and n_ranks(s) == max(ranks)):
            pairs.append((s, r.result))
    return pairs


def kernel_phase(clock: Clock, scenarios, native: bool = True) -> dict:
    """Phase d: one padded oracle bucket through the Pallas kernel
    (compiled natively when ``native``) and through the jnp reference;
    the two must agree within the phase-c envelope."""
    from repro.backends.jax import JaxBatchSimulator
    from repro.core.sweep import next_pow2, scenario_dims

    scenarios = [s for s in scenarios if s.policy == "oracle"]
    check(bool(scenarios), "kernel phase: no oracle scenarios")
    items = [(s.graph, list(s.specs)) for s in scenarios]
    bounds = [s.bound_w for s in scenarios]
    pad = tuple(next_pow2(max(col)) for col in
                zip(*(scenario_dims(s) for s in scenarios)))
    out = {}
    for impl in ("pallas", "ref"):
        with clock.phase(f"d.kernel.{impl}") as f:
            kw = dict(use_kernel=True, kernel_interpret=not native) \
                if impl == "pallas" else {}
            sim = JaxBatchSimulator.padded(
                items, bounds, policy="oracle", dt=DT,
                latency_s=scenarios[0].latency_s, pad_dims=pad, **kw)
            if impl == "pallas":
                check(sim.kernel_interpret is (not native),
                      f"kernel_interpret={sim.kernel_interpret}, "
                      f"native={native}")
            out[impl] = sim.run()
            f.update(rows=len(bounds), ranks=pad[0], jobs_pad=pad[1],
                     kernel_interpret=sim.kernel_interpret
                     if impl == "pallas" else "-")
    dm = max(abs(a.makespan - b.makespan)
             for a, b in zip(out["pallas"], out["ref"]))
    de = max(abs(a.energy_j - b.energy_j) / max(b.energy_j, 1e-12)
             for a, b in zip(out["pallas"], out["ref"]))
    print(f"[smoke] d.kernel: pallas vs ref max |dmakespan|={dm:.6g}s "
          f"max |denergy|/energy={de:.6g}", flush=True)
    check(dm <= MAKESPAN_ATOL and de <= ENERGY_RTOL,
          f"pallas kernel vs ref: |dmakespan|={dm} |denergy|={de}")
    return {"dmakespan_s": dm, "denergy_rel": de}


def service_phase(clock: Clock, corpus=CORPUS, arrivals=ARRIVALS,
                  nodes: int = CLUSTER_NODES) -> None:
    """Phase e: the serve and cluster entry points, both clean."""
    from repro.cluster.cli import main as cluster_main
    from repro.launch.serve import main as serve_main

    with tempfile.TemporaryDirectory() as tmp:
        summary = Path(tmp) / "serve.json"
        with clock.phase("e.serve") as f:
            rc = serve_main(["--trace-corpus", str(corpus), "--executor",
                             "jax", "--expect-clean", "--json",
                             str(summary)])
            check(rc == 0, f"repro.launch.serve returned {rc}")
            s = json.loads(summary.read_text())
            f.update(rc=rc, requests=s["requests"],
                     failures=s["failures"], fallbacks=s["fallbacks"],
                     recompiles=s["recompiles"],
                     compiles_after_warmup=s["compiles_after_warmup"])
        report = Path(tmp) / "cluster.json"
        with clock.phase("e.cluster") as f:
            rc = cluster_main(["run", str(arrivals), "--nodes", str(nodes),
                               "--executor", "jax", "--expect-clean",
                               "--json", str(report)])
            check(rc == 0, f"repro.cluster run returned {rc}")
            pols = json.loads(report.read_text())["policies"]
            replays = [p["replay"] for p in pols if "replay" in p]
            f.update(rc=rc, policies=len(pols), replays=len(replays),
                     fallbacks=sum(r["event_fallbacks"] for r in replays),
                     recompiles=sum(r["recompiles"] for r in replays))


def diff_phase(clock: Clock, steps: int = 3) -> None:
    """Phase f: gradient steps of the learned-policy trainer, float32."""
    import jax
    import numpy as np

    from repro.diff.train import train_policy

    check(not jax.config.jax_enable_x64,
          "x64 is on: the smoke runs the trainer in float32")
    with clock.phase("f.diff") as f:
        params, meta = train_policy(seed=SEED, steps=steps, quick=True,
                                    verbose=False)
        losses = [loss for _, _, loss in meta["loss_history"]]
        check(all(math.isfinite(x) for x in losses),
              f"non-finite loss: {losses}")
        check(all(np.isfinite(v).all() for v in params.values()),
              "non-finite parameters")
        f.update(steps=meta["steps"], scenarios=len(meta["scenarios"]),
                 loss_first=f"{losses[0]:.6g}", loss_last=f"{losses[-1]:.6g}")


def sharded_grid():
    """The phase-b NPB grid at :data:`SHARD_FRACS`, less CG at 256 ranks:
    on one v5e that member (~23k jobs per row) took about 106 of the
    150 s the warm NPB sweep ran on the device, and on four chips every
    second costs four chip-seconds."""
    return [s for s in npb_scenarios(bound_fracs=SHARD_FRACS)
            if not (s.tags["kind"] == "cg" and n_ranks(s) == max(NPB_NODES))]


def placement(scenarios, n_shards: int) -> set:
    """Dispatch each member's bucket sharded ``n_shards`` wide and print
    the device each row range landed on, read from the output's
    sharding.  Returns the device ids used."""
    from repro.core.sweep import build_batch_sim

    groups: dict = {}
    for s in scenarios:
        groups.setdefault((s.name, s.policy_key), []).append(s)
    used = set()
    for (name, policy), scens in groups.items():
        sim = build_batch_sim("jax", scens, [None] * len(scens), True, None,
                              vector_dt=DT, shard_devices=n_shards)
        pending = sim.dispatch()
        shards = sorted((sh.index[0].start or 0, sh.device.id)
                        for sh in pending.out["makespan"].addressable_shards)
        sim.fetch(pending)
        ids = {d for _, d in shards}
        used |= ids
        print(f"[smoke] placement {name}/{policy}: rows={len(scens)} "
              f"row_start->device {shards}", flush=True)
        check(len(ids) == n_shards,
              f"{name}/{policy}: rows landed on {sorted(ids)}, "
              f"not {n_shards} devices")
    return used


def print_buckets(label: str, sweep) -> None:
    """One line per dispatched bucket: member, rows, width, seconds."""
    member = {r.bucket: f"{r.scenario.name}/{r.scenario.policy_key}"
              for r in sweep}
    for b in sweep.profile.buckets:
        print(f"[smoke] {label} bucket {member.get(b.bucket, b.bucket)}: "
              f"rows={b.rows} devices={b.devices} "
              f"compile_s={b.compile_s:.3f} run_s={b.run_s:.3f}",
              flush=True)


def sharded_phase(clock: Clock, scenarios, n_shards: int = SHARD_CHIPS):
    """The four-chip path: the sweep sharded ``n_shards`` wide against
    one device; exact policies' makespans must agree exactly, and every
    bucket's rows must land on ``n_shards`` distinct devices."""
    from repro.policies import get_vector_policy

    # Cold runs only: this path checks placement and agreement, and
    # four chips cost four times as much for each second.  Unpipelined,
    # so that each bucket's run_s is its own device time.
    single = sweep_phase(clock, "npb-1dev", scenarios, warm=False,
                         shard_devices=1, pipeline=False)
    print_buckets("npb-1dev", single)
    sharded = sweep_phase(clock, f"npb-{n_shards}dev", scenarios,
                          warm=False, shard_devices=n_shards, pipeline=False)
    print_buckets(f"npb-{n_shards}dev", sharded)
    widths = {b.devices for b in sharded.profile.buckets}
    with clock.phase("s.compare") as f:
        diffs = [abs(a.result.makespan - b.result.makespan)
                 for a, b in zip(single, sharded)
                 if get_vector_policy(a.scenario.policy).exact]
        # Placement is read from a re-dispatch of the short EP buckets.
        used = placement([s for s in scenarios if s.tags["kind"] == "ep"],
                         n_shards)
        f.update(cells=len(diffs), bucket_widths=sorted(widths),
                 max_dmakespan_s=max(diffs), devices_used=sorted(used))
        check(widths == {n_shards},
              f"buckets ran {sorted(widths)} wide, not {n_shards}")
        check(max(diffs) == 0.0,
              f"sharded makespans differ from one device by {max(diffs)}")
        check(len(used) == n_shards,
              f"rows landed on {sorted(used)}, not {n_shards} devices")


# -------------------------------------------------------------------- main
def run_one_chip(clock: Clock) -> None:
    """Phases b-f."""
    npb = npb_scenarios()
    npb_sweep = sweep_phase(clock, "npb", npb)
    print_buckets("npb.warm", npb_sweep)
    sweep_phase(clock, "family", family_grid(quick=False))
    quick = sweep_phase(clock, "quick", family_grid(quick=True))
    reference_phase(clock, reference_pairs(quick, npb_sweep))
    is_big = [s for s in npb if s.tags.get("kind") == "is"
              and n_ranks(s) == max(NPB_NODES)]
    kernel_phase(clock, is_big)
    service_phase(clock)
    diff_phase(clock)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, SHARD_CHIPS),
                    default=1,
                    help="4 runs only the sharded sweep, on four chips")
    args = ap.parse_args(argv)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    try:
        clock = Clock()
        device = device_check(clock, args.chips)
        from repro.backends.jax import enable_compile_cache

        print(f"[smoke] compile cache: {enable_compile_cache()}",
              flush=True)
        if args.chips == SHARD_CHIPS:
            sharded_phase(clock, sharded_grid())
        else:
            run_one_chip(clock)
    except Exception:  # noqa: BLE001 — the script's boundary: report, fail
        traceback.print_exc()
        print("[smoke] FAILED", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
