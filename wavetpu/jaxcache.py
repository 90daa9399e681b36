"""Where JAX's persistent compilation cache lives, decided in one place.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets no other directory: whoever runs the program places the
cache.  Otherwise the cache goes to `<checkout>/.jax_cache` (gitignored).
The path is fixed on purpose - never a temporary name, a pid or a
timestamp - so that a second process of the same checkout finds what
the first one compiled.

Every entry point calls `configure()` before its first compile (the
solo CLI, the serve front end, the benchmark, chip_smoke.py); so does the
serve engine's program cache, which needs to know whether the cache is
on.  JAX decides at its first compile whether the cache is in use, so a
later `configure()` resets that decision.
"""

from __future__ import annotations

import os
import threading
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


class XlaCacheHitCounter:
    """Counts `/jax/compilation_cache/cache_hits` monitoring events -
    the only signal the in-process XLA cache exposes.  Lets the solo
    CLI (and the fallback serve tier) mark its ledger entry
    `source: disk` when the persistent cache actually served the
    compile, and the solvers' `solve.prepare` span say whether XLA
    compiled.  Best-effort: an older jax without the monitoring hook
    just never counts."""

    def __init__(self):
        self.hits = 0
        self.installed = False
        try:
            from jax._src import monitoring

            def _cb(name, **kw):
                if "compilation_cache/cache_hits" in name:
                    self.hits += 1

            monitoring.register_event_listener(_cb)
            self._cb = _cb
            self.installed = True
        except Exception:
            pass


_XLA_HITS: Optional[XlaCacheHitCounter] = None
_hits_lock = threading.Lock()


def shared_xla_hit_counter() -> XlaCacheHitCounter:
    """One process-wide counter (the monitoring listener cannot be
    unregistered, so per-instance counters would pile up a callback per
    ProgramCache a test suite creates)."""
    global _XLA_HITS
    with _hits_lock:
        if _XLA_HITS is None:
            _XLA_HITS = XlaCacheHitCounter()
        return _XLA_HITS
