"""Optional compiled execution backends.

``repro.core`` stays dependency-light (numpy + scipy); anything that
needs an accelerator stack lives here behind guarded imports.  Current
backends:

* :mod:`repro.backends.jax` — compiled wave-advancement engine
  (``jax.lax.while_loop`` + ``vmap`` over the bound axis) around the
  plain-``jnp`` wave step :mod:`repro.kernels.power_step`;
  ``SweepEngine(executor="jax")``.
"""
