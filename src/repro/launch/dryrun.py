"""Multi-pod dry-run (deliverable e).

For every runnable (architecture x input shape) cell this lowers AND
compiles the real step function against the production mesh — 16x16
single-pod and 2x16x16 multi-pod — with ShapeDtypeStruct inputs (no
allocation).  It records, per cell:

  * memory_analysis(): per-device argument/output/temp/code bytes
    (proves the cell fits 16 GiB v5e HBM),
  * cost_analysis(): HLO FLOPs and bytes accessed,
  * the collective schedule parsed from the compiled (post-SPMD) HLO:
    per-op-kind counts and bytes,

written to results/dryrun/<arch>__<shape>__<mesh>.json for the roofline
report (benchmarks/roofline_report.py reads these artifacts).

This is a CPU-only compile tool: :func:`main` pins JAX to the CPU
platform with 512 host devices before any backend initialises, so it
never takes an accelerator the machine may have (jax locks the platform
and the host device count at first backend initialisation — run it in
a fresh interpreter).
"""

import argparse
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (cell_status, cells, get_config, runnable_cells,
                           shape_by_name)
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   opt_state_shardings, param_shardings,
                                   replicated)
from repro.launch.steps import (abstract_cache, input_specs, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.launch.mesh import dp_axes
from repro.models import abstract_params
from repro.models.sharding import set_policy
from repro.optim import AdamWConfig, init_opt_state

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[0-9,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective result bytes by op kind, from post-SPMD HLO."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_txt)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    return out


def opt_config_for(arch: str) -> AdamWConfig:
    # arctic-480b needs int8 moments to fit a single v5e-256 pod (see
    # repro/optim/adamw.py); everything else keeps fp32 state.
    if arch == "arctic-480b":
        return AdamWConfig(state_dtype="int8")
    return AdamWConfig(state_dtype="float32")


def micro_for(arch: str, shape_name: str) -> int:
    """Gradient-accumulation microbatches per (arch, shape) — the memory
    lever for the densest training cells (activation working set ~ 1/M)."""
    if shape_name != "train_4k":
        return 1
    return {
        "arctic-480b": 16,
        "chameleon-34b": 4,
        "granite-20b": 2,
        "internlm2-20b": 2,
        "moonshot-v1-16b-a3b": 2,
        "llama3-8b": 2,
    }.get(arch, 1)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path = RESULTS, verbose: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    cfg = get_config(arch, shape_name)
    shape = shape_by_name(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)

    params_abs = abstract_params(cfg)
    p_shard = param_shardings(cfg, mesh, params_abs)
    specs = input_specs(cfg, shape)
    set_policy(mesh, dp_axes(mesh))

    with mesh:
        if shape.kind == "train":
            opt_cfg = opt_config_for(arch)
            opt_abs = jax.eval_shape(
                lambda: init_opt_state(params_abs, opt_cfg))
            o_shard = opt_state_shardings(cfg, mesh, opt_abs)
            b_shard = batch_shardings(cfg, mesh, specs)
            accum = jnp.bfloat16 if arch == "arctic-480b" else jnp.float32
            step_fn = make_train_step(cfg, opt_cfg,
                                      n_microbatches=micro_for(arch,
                                                               shape_name),
                                      accum_dtype=accum)
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_shard, o_shard, b_shard, replicated(mesh)),
                out_shardings=(p_shard, o_shard, replicated(mesh)),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, specs,
                                   jax.ShapeDtypeStruct((), jnp.int32))
        elif shape.kind == "prefill":
            b_shard = batch_shardings(cfg, mesh, specs)
            step_fn = make_prefill_step(cfg)
            jitted = jax.jit(step_fn, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(params_abs, specs)
        else:  # decode
            cache_abs = abstract_cache(cfg, shape)
            c_shard = cache_shardings(cfg, mesh, cache_abs)
            tok_shard = batch_shardings(
                cfg, mesh, {"tokens": specs["tokens"]})["tokens"]
            step_fn = make_serve_step(cfg)
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_shard, c_shard, tok_shard,
                              replicated(mesh)),
                donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs, specs["tokens"],
                                   specs["pos"])
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # cost_analysis() returns a dict on recent jax, a 1-element list of
    # dicts on older releases
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo_text = compiled.as_text()
    colls = parse_collectives(hlo_text)
    # loop-corrected totals: while-loop trip counts multiplied through
    # (scan-over-layers/microbatches hide most of the traffic otherwise)
    from repro.core.hlo import collect_collectives

    try:
        _, coll_corrected = collect_collectives(hlo_text)
    except Exception:  # noqa: BLE001 — parsing is best-effort
        coll_corrected = {}

    n_dev = mesh.devices.size
    mem_rec = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
    }
    peak = sum(v for k, v in mem_rec.items()
               if v and k in ("argument_bytes", "output_bytes",
                              "temp_bytes")) \
        - (mem_rec["alias_bytes"] or 0)
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": int(n_dev),
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "memory": mem_rec,
        "peak_bytes_per_device": int(peak),
        "cost": {k: cost.get(k) for k in
                 ("flops", "bytes accessed", "transcendentals",
                  "utilization")
                 if k in cost},
        "collectives_per_device": colls,
        "collectives_per_device_loop_corrected": coll_corrected,
        "n_microbatches": micro_for(arch, shape_name)
        if shape.kind == "train" else 1,
        "compile_seconds": round(time.time() - t0, 1),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    out_path.write_text(json.dumps(record, indent=2))
    if verbose:
        gib = (record["peak_bytes_per_device"] or 0) / 2**30
        coll_mb = sum(v["bytes"] for v in colls.values()) / 2**20
        print(f"[dryrun] {arch:22s} {shape_name:12s} {mesh_name:11s} "
              f"peak/dev={gib:6.2f}GiB  "
              f"flops={record['cost'].get('flops', 0):.3e}  "
              f"coll/dev={coll_mb:9.1f}MiB  "
              f"compile={record['compile_seconds']:6.1f}s", flush=True)
        print(f"  memory_analysis: {mem_rec}", flush=True)
    return record


#: Host devices the production meshes are built from (2 x 16 x 16).
N_HOST_DEVICES = 512


def main(argv=None):
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", N_HOST_DEVICES)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true",
                    help="run every runnable cell")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args(argv)

    out_dir = Path(args.out)
    if args.all:
        todo = runnable_cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        status = cell_status(args.arch, args.shape)
        if status != "run":
            print(f"[dryrun] {args.arch} x {args.shape}: {status}")
            return 0
        todo = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    # The compile-cell batch goes through the shared sweep engine (serial:
    # XLA compilation is not reentrant per process) so failures are
    # captured per cell with timings instead of hand-rolled try/except.
    from repro.core import SweepEngine

    cells = [(arch, shape_name, mp)
             for arch, shape_name in todo for mp in meshes]
    records = SweepEngine(executor="serial").map(
        lambda c: run_cell(c[0], c[1], c[2], out_dir),
        cells,
        label=lambda c: f"{c[0]}__{c[1]}__{'multi' if c[2] else 'single'}")
    failures = [r for r in records if not r.ok]
    for rec in failures:
        print(f"[dryrun] FAIL {rec.label}: {rec.error}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for rec in failures:
            print(f"   {rec.label}: {rec.error[:300]}")
        return 1
    print("\nall dry-run cells compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
