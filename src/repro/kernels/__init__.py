# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# power_step.py is not such a kernel: it is the power-redistribution
# wave step (the per-wave inner loop of the compiled stepper), written
# in plain jnp with the stepper's own shapes and consumed by
# repro.backends.jax.engine, not by the model zoo's ops.py facade.
