"""The plain float32 Qwen2 reference against the program's forward pass and
loss at a tiny size, on the same weights."""
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import DenseDecoder
from bench.program import model_config, program_params
from bench.reference import qwen2
from bench.tests.util import BENCH, TINY_MODEL

CONF = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
CONF.update(TINY_MODEL, torch_dtype="float32")
DIMS = DenseDecoder.from_config(CONF)
THETA, EPS = float(CONF["rope_theta"]), float(CONF["rms_norm_eps"])


def test_weights_repeat_from_the_seed():
    a = qwen2.init_weights(2**40 + 1, DIMS, "float32")
    b = qwen2.init_weights(2**40 + 1, DIMS, "float32")
    c = qwen2.init_weights(2**40 + 2, DIMS, "float32")
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    assert {k: v.shape for k, v in a.items()} == qwen2.weight_shapes(DIMS)


def test_reference_matches_program_forward_and_loss():
    from repro.models import transformer as tf
    w = qwen2.init_weights(3, DIMS, "float32")
    cfg = model_config(CONF)
    params = program_params(w)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda k: tf.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    toks = np.random.default_rng(0).integers(0, DIMS.vocab, 40)
    with jax.default_matmul_precision("highest"):
        prog, _, _ = tf.forward(params, jnp.asarray(toks)[None], cfg)
    idx = jnp.arange(40)
    ref = qwen2.logits_at(w, jnp.asarray(toks), idx, DIMS, THETA, EPS)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog[0]),
                               rtol=1e-4, atol=1e-4)
    labels = np.roll(toks, -1)
    with jax.default_matmul_precision("highest"):
        prog_loss = tf.loss_fn(params, jnp.asarray(toks)[None],
                               jnp.asarray(labels)[None], cfg)
    ref_loss = qwen2.loss(w, jnp.asarray(toks), jnp.asarray(labels), DIMS,
                          THETA, EPS, chunk=16)
    np.testing.assert_allclose(float(ref_loss), float(prog_loss), rtol=1e-5)
