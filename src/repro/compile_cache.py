"""The one home of JAX's persistent compilation cache.

Entry points (chip_smoke.py, benchmarks/run.py, the examples) call
`enable()` once before they compile anything; importing the library never
does.  If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
nothing else is configured.  Otherwise the cache lives at the fixed path
`<checkout>/.jax_cache` — fixed because the directory is part of what a
later process must find again (never a temporary, pid- or time-named one).
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at its home; returns it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
