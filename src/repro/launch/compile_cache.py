"""JAX's persistent compilation cache, configured in one place.

Every entry point that compiles for the chip (``chip_smoke.py``,
``launch/train.py``, ``launch/serve_classifier.py``, ``benchmarks/run.py``)
calls ``enable()`` before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  else is configured here;
* unset: the cache lives in ``.jax_cache/`` at the checkout root — a
  fixed path, because the path is part of what a later process looks
  up, so a temp-, pid- or time-based directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
