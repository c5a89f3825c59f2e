"""Benchmark harness — one module per paper table/figure (deliverable d).

Bench modules are dispatched through :class:`repro.core.SweepEngine.map`
(serial by design: each bench prints its own table), which captures
per-bench failures instead of aborting the suite.  Prints
``name,us_per_call,derived`` CSV at the end.  ``--full`` runs the heavier
class-C / 9-point variants; ``--list-policies`` shows the power-policy
registry the simulator benches draw from.
"""

from __future__ import annotations

import argparse
import inspect
import sys


#: The benchmark registry: name -> (module attribute path, one-line
#: description).  ``--list`` prints it; unknown ``--only`` names fail
#: against it with the available set.
BENCHES = {
    "depth_tables": ("depth_tables", "Tables I & II: policy depth vs "
                     "makespan on the Listing-2 graphs"),
    "fig8": ("fig8_power_sweep", "Fig. 8 power sweep (+ uniform §VI "
             "variant) on the 500-cell grid"),
    "fig9": ("fig9_stddev_sweep", "Fig. 9 skew (stddev) sweep"),
    "npb": ("npb_analogues", "Figs. 11-13 NPB analogue workloads "
            "(IS/EP/CG)"),
    "family": ("family_sweep", "mixed-shape scenario families as "
               "padded batched buckets"),
    "sharded": ("sharded_sweep", "multi-device sharded sweep scaling"),
    "trace-replay": ("trace_replay", "MPI trace corpus ingest + "
                     "calibrated replay sweep"),
    "serve": ("serve_stream", "streaming SweepService under a Poisson "
              "open-loop load"),
    "cluster": ("cluster_sched", "outer cluster policies over the "
                "bundled 1k-job arrival trace"),
    "lm_workloads": ("lm_workloads", "pipeline-parallel / MoE "
                     "training-step graphs"),
    "roofline": ("roofline_report", "§Roofline table: kernel arithmetic "
                 "intensity"),
    "diff": ("diff_opt", "gradient-optimized caps vs paper ILP + "
             "learned-policy OOD sweep (needs jax)"),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="full problem classes / sweep resolutions")
    ap.add_argument("--only", "--workload", dest="only", default=None,
                    help="comma-separated bench names (see --list)")
    ap.add_argument("--list", action="store_true",
                    help="list available benchmarks and exit")
    ap.add_argument("--list-policies", action="store_true",
                    help="list registered power policies and exit")
    ap.add_argument("--backend", choices=("event", "vector", "jax"),
                    default="event",
                    help="simulator backend for benches that support it "
                         "(vector/jax also print an event-vs-vector[-jax] "
                         "timing comparison; jax needs the [jax] extra "
                         "and falls back to vector otherwise)")
    ap.add_argument("--bench-json", default="BENCH_sweep.json",
                    help="where to write the machine-readable benchmark "
                         "artifact (written only when a bench deposits "
                         "records, i.e. with --backend vector/jax)")
    args = ap.parse_args(argv)
    quick = not args.full

    if args.list:
        for name, (_, desc) in BENCHES.items():
            print(f"{name:<14s} {desc}")
        return 0

    if args.list_policies:
        from repro.policies import available_policies, get_policy

        for name in available_policies():
            cls = type(get_policy(name))
            doc = (cls.__doc__ or sys.modules[cls.__module__].__doc__
                   or "").strip().splitlines()[0]
            print(f"{name:<14s} {cls.__name__:<24s} {doc}")
        return 0

    import importlib

    from repro.backends.jax import enable_compile_cache
    from repro.core import SweepEngine

    enable_compile_cache()

    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = sorted(only - set(BENCHES))
        if unknown:
            ap.error(f"unknown benchmark(s) {', '.join(unknown)}; "
                     f"available: {', '.join(BENCHES)}")
    todo = [(name, importlib.import_module(f".{mod}", __package__).main)
            for name, (mod, _) in BENCHES.items()
            if not only or name in only]

    def run_bench(item):
        name, fn = item
        print(f"\n{'=' * 70}\n== {name}\n{'=' * 70}")
        kwargs = {"quick": quick}
        if "backend" in inspect.signature(fn).parameters:
            kwargs["backend"] = args.backend
        return fn(**kwargs)

    records = SweepEngine(executor="serial").map(
        run_bench, todo, label=lambda item: item[0])

    lines = []
    for rec in records:
        if rec.ok:
            lines.extend(rec.value)
        else:
            print(f"BENCH FAILURE {rec.label}: {rec.error}")
            lines.append(f"{rec.label},0.0,FAILED")

    print("\n--- CSV (name,us_per_call,derived) ---")
    for line in lines:
        print(line)

    from .common import write_bench_json

    if write_bench_json(args.bench_json):
        print(f"\nwrote {args.bench_json}")
    return 0 if all(rec.ok for rec in records) else 1


if __name__ == "__main__":
    sys.exit(main())
