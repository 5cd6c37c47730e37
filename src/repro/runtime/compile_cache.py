"""Where JAX keeps compiled programs between processes.

A cold step of a full-width model compiles for minutes; the persistent
compilation cache lets the next process on the same machine skip that.
"""

from __future__ import annotations

import os
import pathlib

import jax

# A fixed path: the cache only hits when the directory does not move.
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout.  Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
