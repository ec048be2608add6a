"""Continuous-batching engine: outputs must equal sequential generation
(the `greedy_reference` fixture, tests/conftest.py)."""
import jax
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.models import transformer as tf
from repro.serve.engine import PagedServeEngine, Request


@pytest.mark.slow
def test_engine_matches_sequential(greedy_reference):
    cfg = smoke_config("qwen2-0.5b")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, rs.randint(3, 9)).tolist()
               for _ in range(6)]
    n_new = 5

    engine = PagedServeEngine(cfg, params, slots=2, max_len=64)
    for i, p in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=p, max_new_tokens=n_new))
    finished = engine.run()
    assert len(finished) == len(prompts)

    by_rid = {r.rid: r.output for r in finished}
    for i, p in enumerate(prompts):
        ref = greedy_reference(cfg, params, p, n_new)
        assert by_rid[i] == ref, (i, by_rid[i], ref)


@pytest.mark.slow
def test_engine_more_requests_than_slots_and_eos():
    cfg = smoke_config("gemma2-2b")   # local+global attention exercised
    params = tf.init_params(jax.random.PRNGKey(1), cfg)
    engine = PagedServeEngine(cfg, params, slots=2, max_len=48)
    for i in range(5):
        engine.submit(Request(rid=i, prompt=[1 + i, 2, 3],
                              max_new_tokens=4))
    finished = engine.run()
    assert len(finished) == 5
    assert all(len(r.output) == 4 for r in finished)


@pytest.mark.slow
def test_engine_abft_verify_identical():
    cfg = smoke_config("qwen2-0.5b")
    params = tf.init_params(jax.random.PRNGKey(2), cfg)
    outs = {}
    for mode in ("off", "verify"):
        engine = PagedServeEngine(cfg, params, slots=2, max_len=48,
                                  abft_mode=mode)
        for i in range(3):
            engine.submit(Request(rid=i, prompt=[5, 6, 7], max_new_tokens=4))
        outs[mode] = {r.rid: r.output for r in engine.run()}
    assert outs["off"] == outs["verify"]
