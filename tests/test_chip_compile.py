"""Compiles for a described TPU v5e: the wave step ``power_step`` and the
compiled sweep stepper, built by the TPU compiler with no chip attached.
Nothing runs, so these say nothing of results or times; they catch what
the CPU cannot (layouts, memory limits, lowering).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers import
every test file.  The persistent compile cache is off in this file,
since an entry compiled for a described chip cannot be read back.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.backends.jax.engine import _run_batch  # noqa: E402
from repro.core import heterogeneous_cluster, is_like, lu_like  # noqa: E402
from repro.core.power import (max_useful_cluster_bound,  # noqa: E402
                              min_feasible_cluster_bound)
from repro.core.sweep import (Scenario, build_batch_sim,  # noqa: E402
                              next_pow2, scenario_dims)
from repro.kernels.power_step import StepTables, power_step  # noqa: E402

#: Rows of the vmapped step (a bucket's bound axis).
ROWS = 8
#: LUT states, padded as the engine pads them.
STATES = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _abstract(tree, sharding):
    """Shapes and dtypes of a pytree of host arrays, on ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("redistribute", [False, True])
@pytest.mark.parametrize("n", [12, 64, 256])
def test_power_step_compiles(one_chip, n, redistribute):
    """The step vmapped over a stacked bucket's rows, at the cells'
    widths; only the redistributing step holds the water-fill's loop."""
    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tab = StepTables(spec(ROWS, STATES, n), spec(ROWS, STATES, n),
                     *[spec(ROWS, n)] * 7)
    lane = spec(ROWS, n)
    step = jax.jit(jax.vmap(
        lambda tab, caps, running, remaining, rho, bound: power_step(
            tab, caps, running, remaining, rho, bound, redistribute)))
    compiled = step.lower(tab, lane, spec(ROWS, n, dtype=jnp.bool_), lane,
                          lane, spec(ROWS)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert (" while(" in text) == redistribute


def _bucket(graph: str, policy: str):
    """A two-bound bucket as the sweep engine builds it: IS-64 stacked
    into its power-of-two envelope, LU-64 on its one shared graph."""
    if graph == "is64":
        g, shared = is_like(64, "B", seed=5), False
    else:
        g, shared = lu_like(64, "B", iterations=2), True
    specs = tuple(heterogeneous_cluster(64, seed=5))
    lo = min_feasible_cluster_bound(specs)
    hi = max_useful_cluster_bound(specs)
    scens = [Scenario(graph, g, specs, lo + f * (hi - lo), policy)
             for f in (0.3, 0.6)]
    dims = tuple(next_pow2(d) for d in scenario_dims(scens[0]))
    return build_batch_sim("jax", scens, [None] * len(scens), shared, dims)


@pytest.mark.parametrize("policy", ["equal-share", "oracle"])
@pytest.mark.parametrize("graph", ["is64", "lu64"])
def test_sweep_stepper_compiles(one_chip, graph, policy):
    """One NPB bucket at 64 ranks through the stepper the engine
    dispatches, with the statics it dispatches with."""
    sim = _bucket(graph, policy)
    args, statics = sim._pack()
    assert set(statics) == {"policy_name", "wants_ticks", "redistribute",
                            "max_steps", "stacked"}
    assert statics["stacked"] is (graph == "is64")
    compiled = _run_batch.lower(*_abstract(args, one_chip),
                                **statics).compile()
    assert "tpu_custom_call" not in compiled.as_text()
