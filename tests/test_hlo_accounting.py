"""The trip-count-aware HLO accountant must be exact on known-FLOP programs
(XLA's own cost_analysis counts loop bodies once — the bug this fixes)."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.launch.hlo_accounting import account


def _flops(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return account(txt)["flops"]


M = 128
A = jnp.ones((M, M), jnp.float32)


def test_plain_dot():
    assert _flops(lambda a: a @ a, A) == 2 * M ** 3


def test_scan_multiplies_by_trip_count():
    def scanned(a):
        return lax.scan(lambda x, _: (x @ a, None), a, None, length=8)[0]
    assert _flops(scanned, A) == 8 * 2 * M ** 3


def test_nested_scans():
    def nested(a):
        def outer(x, _):
            return lax.scan(lambda y, __: (y @ a, None), x, None, length=4)[0], None
        return lax.scan(outer, a, None, length=8)[0]
    assert _flops(nested, A) == 32 * 2 * M ** 3


def test_fori_loop():
    def f(a):
        return lax.fori_loop(0, 5, lambda i, x: x @ a, a)
    assert _flops(f, A) == 5 * 2 * M ** 3


def test_batched_einsum():
    B = jnp.ones((4, M, M), jnp.float32)
    got = _flops(lambda b: jnp.einsum("bij,bjk->bik", b, b), B)
    assert got == 4 * 2 * M ** 3


def test_grad_through_scan():
    def scanned(a):
        return lax.scan(lambda x, _: (x @ a, None), a, None, length=8)[0]

    def loss(a):
        return jnp.sum(scanned(a) ** 2)
    # fwd 8 dots + bwd 2x8 dots
    assert _flops(jax.grad(loss), A) == 24 * 2 * M ** 3


def test_xla_cost_analysis_undercounts():
    """Documents WHY this module exists."""
    def scanned(a):
        return lax.scan(lambda x, _: (x @ a, None), a, None, length=8)[0]
    ca = jax.jit(scanned).lower(A).compile().cost_analysis()
    # ~1/8 of the truth (one loop body + the s32 counter add)
    assert ca["flops"] < 2 * M ** 3 + 16


def test_dynamic_slice_bytes_not_full_operand():
    """A sliced stacked tensor must not count the full stack per iteration."""
    big = jnp.ones((64, M, M), jnp.float32)

    def f(stack):
        def body(acc, i):
            return acc + lax.dynamic_index_in_dim(stack, i, 0, False), None
        return lax.scan(body, jnp.zeros((M, M)), jnp.arange(64))[0]
    r = account(jax.jit(f).lower(big).compile().as_text())
    # full-stack-per-iter would be 64 * 64*M*M*4 = 268 MB; slice-aware ~ 12 MB
    assert r["bytes"] < 64 * M * M * 4 * 10
