"""Where JAX keeps its persistent compilation cache for the entry points.

The cache key includes the directory, so a directory that moves between
runs never hits. The rule, applied by ``chip_smoke.py``,
``repro.launch.train`` and ``repro.launch.serve`` before their first
compile (tests never call it):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set here.
* unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path inside
  the checkout, never one made from a temporary name, a process id or the
  time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root, four levels above this file (src/repro/launch/)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure() -> str:
    """Place the persistent compilation cache; returns its directory."""
    import jax
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
