"""The MoE cell's pieces on the CPU at a tiny size: the plain reference
against the program (logits, loss, gradients), the FLOP count, and whole
runs of the driver through the harness, correct when sound and not
correct under the int8 control and the planted faults."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.flops_moe import MoEDecoder
from bench.program_moe import bench_leaves, model_config, program_params
from bench.reference import qwen3_moe
from bench.tests import util

CONF = json.loads((util.BENCH / "configs" / "qwen3-moe-30b-a3b.json")
                  .read_text())
# d 64, 8 experts routed top-2, 2 held per share (from expert 2), vocab 512
TINY_MOE = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                head_dim=16, moe_intermediate_size=32, num_hidden_layers=2,
                vocab_size=512, num_experts=2, expert_offset=2,
                num_experts_per_tok=2,
                published=dict(CONF["published"], num_experts=8))
THETA, EPS = float(CONF["rope_theta"]), float(CONF["rms_norm_eps"])
COEF = float(CONF["router_aux_loss_coef"])


def _conf(**kw):
    return dict(CONF, **TINY_MOE, **kw)


def test_weights_repeat_from_the_seed():
    dims = MoEDecoder.from_config(_conf())
    a = qwen3_moe.init_weights(2**40 + 1, dims, "float32")
    b = qwen3_moe.init_weights(2**40 + 1, dims, "float32")
    c = qwen3_moe.init_weights(2**40 + 2, dims, "float32")
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["w_gate"], c["w_gate"])
    assert {k: v.shape for k, v in a.items()} == qwen3_moe.weight_shapes(dims)


def test_reference_matches_program_logits_loss_and_gradients():
    from repro.models import transformer as tf
    conf = _conf(torch_dtype="float32")
    dims = MoEDecoder.from_config(conf)
    cfg = model_config(conf)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset) == (8, 2, 2)
    w = qwen3_moe.init_weights(3, dims, "float32")
    params = program_params(w)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda k: tf.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 40)))
    labels = jnp.roll(toks, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        prog = tf.forward(params, toks, cfg)[0]
        ref = qwen3_moe.logits_at(w, toks, dims, THETA, EPS, q_block=16)
        lp, gp = jax.value_and_grad(lambda p: tf.loss_fn(
            p, toks, labels, cfg, aux_weight=COEF))(params)
        lr, gr = jax.value_and_grad(lambda w: qwen3_moe.loss(
            w, toks, labels, dims, THETA, EPS, COEF, chunk=16,
            q_block=16))(w)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(float(lr), float(lp), rtol=1e-5)
    gp = bench_leaves(gp)
    for k in gr:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gr[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_flop_split_of_the_cell():
    """At 2 x 8192 tokens attention (projections and scores) is about 82%
    of the model FLOPs, the head over the slice 10%, the chip's share of
    the experts 7% (the cell's `why`)."""
    parts = MoEDecoder.from_config(CONF).forward_parts(2, 8192)
    total = sum(parts.values())
    share = {k: v / total for k, v in parts.items()}
    assert share["attention"] == pytest.approx(0.82, abs=0.01)
    assert share["head"] == pytest.approx(0.10, abs=0.01)
    assert share["expert"] == pytest.approx(0.07, abs=0.01)
    assert MoEDecoder.from_config(CONF).train_flops(2, 8192) == \
        pytest.approx(37.7e12, rel=0.01)


TINY_CELL = "tiny.train-moe"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    b = util.tiny_bench(tmp_path_factory.mktemp("checkout"))
    (b / "configs" / "tiny-qwen3-moe.json").write_text(json.dumps(_conf()))
    work = json.loads((util.BENCH / "workloads" /
                       "qwen3-moe-30b-a3b.train-8k.json").read_text())
    # bf16 at this size: sound runs (seeds 1-12) read loss 2.1e-3, grad
    # 4.4e-2, change 1.1e-2 at most (bf16 routing flips near-ties); the
    # int8 control (seeds 13-15: loss 6.2e-3 and more, grad 7.9 and more)
    # and each fault (loss 5.5e-3, grad 0.27, change 0.24 and more) read
    # above these limits in one number at least
    work.update(config="tiny-qwen3-moe", traffic="tiny-train-moe",
                limits={"loss_gap": 4e-3, "grad1_gap": 0.1,
                        "change_gap": 0.05, "unrouted_rows": 0})
    (b / "workloads" / f"{TINY_CELL}.json").write_text(json.dumps(work))
    (b / "traffic" / "tiny-train-moe.json").write_text(json.dumps(
        {"kind": "batches", "seq_len": 32, "batch": 2, "zipf_a": 1.2}))
    spec = json.loads((b.parent / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "qwen3-moe-30b-a3b.train-8k" in m.get("workloads", ()):
            m["workloads"].append(TINY_CELL)
    (b.parent / "BENCHMARK.json").write_text(json.dumps(spec))
    return b


def test_moe_train_run_is_correct(bench):
    r = util.run_cell(bench, TINY_CELL, seed=2**33 + 7)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 3 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert r["checks"]["unrouted_rows"]["value"] == 0


def test_moe_traced_run_reads_its_per_layer_metrics(bench):
    r = util.run_cell(bench, TINY_CELL, seed=5, trace=True)
    # the CPU trace has no TPU plane: only the host-clock and counter
    # metrics read anything (their numbers mean nothing here)
    assert set(r["metrics"]) == {"train_mfu.moe",
                                 "expert_gemm_pad_share.moe"}
    assert 0 < r["metrics"]["expert_gemm_pad_share.moe"]["value"] < 100


@pytest.mark.parametrize("hook", ["control", "half_batch", "frozen_state"])
def test_moe_control_and_faults_are_not_correct(bench, hook):
    plant = util.control if hook == "control" else util.fault(hook)
    r = util.run_cell(bench, TINY_CELL, seed=13, hook=plant)
    assert not r["correct"], r["checks"]
