"""JAX's persistent compilation cache, shared by every entry point.

`cli.main`, `chip_smoke.py` and `bench.py` call `enable()` before any
compile. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and nothing is set in code. Otherwise the cache lives at a fixed
`.jax_cache/` in the checkout: the directory is part of what a later
process looks up, so it is never a temp name, a pid or a time.
"""

from __future__ import annotations

import os

CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable() -> str:
    """→ the directory compiled programs are cached in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR
