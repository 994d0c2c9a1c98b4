"""The one home of JAX's persistent compilation cache.

Entry points (chip_smoke.py, bench/run.py, the examples) call
`enable()` once before they compile anything; importing the library never
does.  If `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
nothing else is configured.  Otherwise the cache lives at the fixed path
`<checkout>/.jax_cache` — fixed because the directory is part of what a
later process must find again (never a temporary, pid- or time-named one).

The key holds the programs' metadata: JAX's default leaves it out, so an
executable compiled from a program with other named scopes or source lines
would be loaded, and profiles would show its stale scopes (the device
trace's `tf_op`).  Source paths are cut to the checkout, so the key does not
depend on where the checkout lies.
"""
from __future__ import annotations

import os
import re

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at its home; returns it."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
