"""Where JAX's persistent compilation cache lives for this repo's programs.

The cache's directory is part of what makes an entry findable, so every
entry point (``chip_smoke.py``, ``python -m repro.launch.probe``,
``python -m repro.fleet``, ``python -m benchmarks.run``) places it through
``setup_compile_cache`` and fleet workers share their parent's entries.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: fixed, never derived from a pid, a time or a
# temporary name (a path that moves never hits)
DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    path is set here; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
