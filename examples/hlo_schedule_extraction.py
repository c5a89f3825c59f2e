"""The paper's MPI-wrapper, TPU-style: extract the job dependency graph
from a *compiled, unmodified* JAX training step and schedule its power.

The paper builds its dependency graph by intercepting MPI calls
(§VII-A1).  Here the compiled HLO already names every collective, so we
parse the schedule out of ``compiled.as_text()``, build the job graph,
and run the ILP + online heuristic on it — zero model-code changes.

NOTE: sets XLA_FLAGS for 8 host devices; run as a standalone script.

Run:  PYTHONPATH=src python examples/hlo_schedule_extraction.py
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import sys  # noqa: E402

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_smoke  # noqa: E402
from repro.core import compare_policies  # noqa: E402
from repro.core.hlo_extract import describe_schedule, step_job_graph  # noqa: E402
from repro.core.power import NodeSpec, tpu_v5e_lut  # noqa: E402
from repro.launch.sharding import batch_shardings, param_shardings  # noqa: E402
from repro.launch.steps import input_specs, make_train_step  # noqa: E402
from repro.models import abstract_params  # noqa: E402
from repro.models.sharding import set_policy  # noqa: E402
from repro.optim import AdamWConfig, init_opt_state  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402


def main():
    cfg = get_smoke("llama3-8b")
    shape = ShapeConfig("mini_train", seq_len=128, global_batch=8,
                        kind="train")
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    set_policy(mesh, "data")

    params_abs = abstract_params(cfg)
    p_shard = param_shardings(cfg, mesh, params_abs)
    specs = input_specs(cfg, shape)
    b_shard = batch_shardings(cfg, mesh, specs)
    opt_cfg = AdamWConfig()
    opt_abs = jax.eval_shape(lambda: init_opt_state(params_abs, opt_cfg))
    from repro.launch.sharding import opt_state_shardings, replicated

    o_shard = opt_state_shardings(cfg, mesh, opt_abs)
    with mesh:
        compiled = jax.jit(
            make_train_step(cfg, opt_cfg),
            in_shardings=(p_shard, o_shard, b_shard, replicated(mesh)),
            out_shardings=(p_shard, o_shard, replicated(mesh)),
        ).lower(params_abs, opt_abs, specs,
                jax.ShapeDtypeStruct((), jnp.int32)).compile()

    hlo = compiled.as_text()
    sched = describe_schedule(hlo)
    print(f"collective schedule of the compiled train step "
          f"({len(sched)} ops):")
    for kind, nbytes in sched[:12]:
        print(f"  {kind:<20s} {nbytes / 1024:8.1f} KiB/device")
    if len(sched) > 12:
        print(f"  ... {len(sched) - 12} more")

    # -> the paper's abstraction, scheduled under a power bound
    n_hosts = 4
    graph = step_job_graph(hlo, n_nodes=n_hosts, total_work=100.0,
                           skew=0.25)
    print(f"\nextracted job graph: {graph.stats()}")
    specs_p = [NodeSpec(tpu_v5e_lut()) for _ in range(n_hosts)]
    P = sum(s.lut.idle_w + 0.3 * (s.lut.p_min - s.lut.idle_w)
            for s in specs_p)
    res = compare_policies(graph, specs_p, P, ilp_time_limit=60.0)
    eq = res["equal-share"]
    print(f"power scheduling of the extracted step graph "
          f"(bound {P:.0f} W):")
    for name, r in res.items():
        print(f"  {name:<12s} makespan {r.makespan:8.2f}  "
              f"speedup {eq.makespan / r.makespan:5.2f}x")


if __name__ == "__main__":
    main()
