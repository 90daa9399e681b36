"""Where JAX's persistent compilation cache lives, decided in one place.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets no other directory: whoever runs the program places the
cache.  Otherwise the cache goes to `<checkout>/.jax_cache` (gitignored).
The path is fixed on purpose - never a temporary name, a pid or a
timestamp - so that a second process of the same checkout finds what
the first one compiled.

Every entry point calls `configure()` before its first compile (the
solo CLI, the serve front end, bench.py, chip_smoke.py); so does the
serve engine's program cache, which needs to know whether the cache is
on.  JAX decides at its first compile whether the cache is in use, so a
later `configure()` resets that decision.
"""

from __future__ import annotations

import os
from typing import Optional

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure() -> Optional[str]:
    """Turn on the persistent compilation cache; return its directory,
    or None where the process turned the cache off
    (`jax_enable_compilation_cache`, as the test suite does)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not jax.config.jax_enable_compilation_cache:
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        compilation_cache.reset_cache()
    return DEFAULT_DIR
