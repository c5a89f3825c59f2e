"""Compiled JAX execution backend (guarded: importable without jax).

Importing this package never pulls in jax; the engine modules load
lazily on first attribute access.  Check :data:`HAS_JAX` (or call
:func:`jax_available`) before touching the engine from code that must
run in jax-free environments — :class:`~repro.core.sweep.SweepEngine`
does exactly that and falls back to the vector backend.

Public surface::

    from repro.backends.jax import JaxBatchSimulator, simulate_batch_jax
    from repro.backends.jax.policy_fns import jax_policies
"""

from __future__ import annotations

import importlib.util
import os
from pathlib import Path
from typing import Optional

#: True when the ``jax`` package is installed (cheap spec probe — does
#: not import jax, so this is safe at module scope).
HAS_JAX = importlib.util.find_spec("jax") is not None

#: Where :func:`enable_compile_cache` keeps compiled programs when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set: ``<checkout>/.jax_cache``.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[4] / ".jax_cache"


def jax_available() -> bool:
    return HAS_JAX


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; returns its directory
    (``None`` when jax is not installed).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is changed.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR` — a fixed path, so a later run in the
    same checkout finds what an earlier one compiled.  Only entry
    points call this (never an import), so library callers and the
    tests keep JAX's default of no persistent cache.
    """
    if not HAS_JAX:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_LAZY = {
    "JaxBatchSimulator": "engine",
    "simulate_batch_jax": "engine",
    "shard_count": "engine",
    "stepper_cache_size": "engine",
    "JaxPolicy": "policy_fns",
    "get_jax_policy": "policy_fns",
    "has_jax_policy": "policy_fns",
    "jax_policies": "policy_fns",
}


def __getattr__(name: str):
    module = _LAZY.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    if not HAS_JAX:
        raise ImportError(
            f"{__name__}.{name} requires jax; install the optional "
            f"dependency group: pip install -e .[jax]")
    import importlib

    mod = importlib.import_module(f"{__name__}.{module}")
    return getattr(mod, name)


__all__ = ["HAS_JAX", "CHECKOUT_CACHE_DIR", "enable_compile_cache",
           "jax_available", *_LAZY]
