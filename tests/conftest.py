"""Shared fixtures.  NOTE: no XLA_FLAGS here — the main pytest process must
see 1 CPU device (multi-device tests go through subprocesses, and only
launch/dryrun.py forces 512 placeholder devices)."""
import numpy as np
import pytest


@pytest.fixture
def rs():
    return np.random.RandomState(0)


def _sequential_generate(cfg, params, prompt, n_new):
    """Single-request greedy decode over a dense 64-position cache: the
    first ``n_new`` tokens, the prefill's argmax first."""
    import jax.numpy as jnp
    from repro.models import transformer as tf

    cache = tf.init_cache(cfg, 1, 64)
    toks = jnp.asarray([prompt], jnp.int32)
    logits, cache, _ = tf.forward(params, toks, cfg, cache=cache)
    out = [int(jnp.argmax(logits[0, -1]))]
    for i in range(n_new - 1):
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        logits, cache = tf.decode_step(
            params, tok, jnp.asarray(len(prompt) + i), cache, cfg)
        out.append(int(jnp.argmax(logits[0])))
    return out


@pytest.fixture(scope="session")
def greedy_reference():
    """The serve engine's reference: ``(cfg, params, prompt, n_new) ->
    tokens`` of single-request greedy decoding."""
    return _sequential_generate
