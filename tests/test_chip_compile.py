"""Compiles for a described TPU v5e: the Pallas ``power_step`` kernel and
the compiled sweep stepper, built by the TPU compiler with no chip
attached.  Nothing runs, so these say nothing of results or times; they
catch what interpret mode cannot (tiling, fast-memory limits, lowering).

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

from repro.backends.jax import JaxBatchSimulator  # noqa: E402
from repro.backends.jax.engine import _run_batch  # noqa: E402
from repro.core import is_like, heterogeneous_cluster  # noqa: E402
from repro.core.power import (max_useful_cluster_bound,  # noqa: E402
                              min_feasible_cluster_bound)
from repro.core.sweep import next_pow2, scenario_dims, Scenario  # noqa: E402
from repro.kernels.power_step import StepTables, power_step_pallas  # noqa: E402

#: Rows of the vmapped kernel call (a bucket's bound axis).
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
def test_power_step_kernel_compiles(one_chip, n, redistribute):
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    tab = StepTables(spec(ROWS, STATES, n), spec(ROWS, STATES, n),
                     *[spec(ROWS, 1, n)] * 7)
    lane, scalar = spec(ROWS, 1, n), spec(ROWS, 1, 1)
    step = jax.jit(jax.vmap(
        lambda tab, caps, running, remaining, rho, bound: power_step_pallas(
            tab, caps, running, remaining, rho, bound,
            redistribute=redistribute, interpret=False)))
    compiled = step.lower(tab, lane, lane, lane, lane, scalar).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_sweep_stepper_compiles(one_chip, impl):
    """One padded oracle NPB-IS bucket at 64 ranks, as the sweep engine
    builds it, through the stepper the engine dispatches."""
    graph = is_like(64, "B", seed=5)
    specs = heterogeneous_cluster(64, seed=5)
    lo = min_feasible_cluster_bound(specs)
    hi = max_useful_cluster_bound(specs)
    bounds = [lo + f * (hi - lo) for f in (0.3, 0.6)]
    dims = scenario_dims(Scenario("is64", graph, tuple(specs), bounds[0],
                                  "oracle"))
    sim = JaxBatchSimulator.padded(
        [(graph, specs)] * len(bounds), bounds, policy="oracle",
        use_kernel=impl == "pallas", kernel_interpret=False,
        pad_dims=tuple(next_pow2(d) for d in dims))
    args, statics = sim._pack()
    assert statics["impl"] == impl and statics["interpret"] is False
    compiled = _run_batch.lower(*_abstract(args, one_chip),
                                **statics).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (impl == "pallas")
