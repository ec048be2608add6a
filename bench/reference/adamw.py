"""Plain AdamW with global-norm clipping and a warm-up + cosine schedule.

Written from the algorithm (Loshchilov & Hutter, "Decoupled Weight Decay
Regularization"), with the hyper-parameters the workload file states.
Moments are float32; parameters are stored in the dtype the configuration
states (``store_dtype``) after every update, as a model served in that
dtype is.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def lr_at(hp: Dict, count: int) -> float:
    warm = min(count / max(hp["warmup_steps"], 1), 1.0)
    frac = min(max((count - hp["warmup_steps"])
                   / max(hp["total_steps"] - hp["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    r = hp["min_lr_ratio"]
    return hp["lr"] * warm * (r + (1.0 - r) * cos)


def init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params)}


def _update(params, grads, opt, lr, count, hp, store_dtype):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-12))
    b1, b2 = hp["b1"], hp["b2"]
    b1c, b2c = 1.0 - b1 ** count, 1.0 - b2 ** count

    def one(p, g, m, v):
        g = g * scale
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        step = (m / b1c) / (jnp.sqrt(v / b2c) + hp["eps"])
        p32 = p.astype(jnp.float32)
        p_new = p32 - lr * (step + hp["weight_decay"] * p32)
        return p_new.astype(store_dtype), m, v

    out = jax.tree.map(one, params, grads, opt["m"], opt["v"])
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), {"m": pick(1), "v": pick(2)}, scale


def update(params, grads, opt, count: int, hp: Dict, store_dtype):
    """One step; returns (params, opt, clip scale): the optimizer gets the
    gradient times the scale.  ``count`` is the 1-based step number."""
    return _update_jit(params, grads, opt, jnp.float32(lr_at(hp, count)),
                       count, _Frozen(hp), jnp.dtype(store_dtype))


_update_jit = jax.jit(_update, static_argnums=(4, 5, 6), donate_argnums=(0, 2))


class _Frozen(dict):
    """A hashable view of the hyper-parameters (a static jit argument)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
