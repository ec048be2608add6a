"""JAX's persistent compilation cache, placed from outside.

`enable()` is called by the entry points that compile (the `launch` CLIs,
`benchmarks/run.py`, `chip_smoke.py`) and never on `import repro`, so a
library user keeps whatever cache setting they chose.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, since the path is part of the cache key.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable"]

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
