"""Where JAX keeps its persistent compilation cache.

The launchers (`launch/solve.py`, `launch/serve_solver.py`) and
`chip_smoke.py` call `enable_compile_cache()` before their first compile;
importing the library never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already keeps its cache there and no other path is set.
Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored): a
fixed path, because the path is part of what a later run looks up, and a
directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts: Dict[str, int] = {"hits": 0, "misses": 0}
_enabled = False


def _count(event: str, **_) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _counts[key] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process (every compile is
    cached, however short) and return its directory."""
    global _enabled
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _enabled:
        jax.monitoring.register_event_listener(_count)
        _enabled = True
    return path


def cache_counts() -> Dict[str, int]:
    """Persistent-cache hits and misses (writes) seen by this process
    since `enable_compile_cache`."""
    return dict(_counts)
