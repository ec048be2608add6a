"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests / benches see 1 device).
"""
from __future__ import annotations

from repro.dist.sharding import make_mesh

__all__ = ["make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
