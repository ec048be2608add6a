"""End-to-end behaviour tests for the paper's system: train through
failures, resume exactly, ABFT-on training parity, serving."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.train import run as train_run
from repro.launch.serve import run as serve_run


@pytest.mark.slow
def test_training_converges_through_failures(tmp_path):
    """The paper's stress discipline applied to LM training: loss must
    decrease across injected DP-shard losses + diskless recoveries."""
    losses = train_run("qwen2-0.5b", smoke=True, steps=40, batch=8, seq=64,
                       inject_failures=2, ckpt_dir=str(tmp_path),
                       log_every=100, diskless_every=5)
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.slow
def test_resume_is_exact(tmp_path):
    """Checkpoint/restart: 8+8 steps == 16 steps (same data, same rng)."""
    l_full = train_run("xlstm-350m", smoke=True, steps=16, batch=4, seq=32,
                       log_every=100)
    d = str(tmp_path / "ck")
    train_run("xlstm-350m", smoke=True, steps=8, batch=4, seq=32,
              ckpt_dir=d, log_every=100, total_steps=16)
    l_resumed = train_run("xlstm-350m", smoke=True, steps=16, batch=4, seq=32,
                          ckpt_dir=d, resume=True, log_every=100)
    # the resumed run's final losses must match the uninterrupted run
    np.testing.assert_allclose(l_resumed[-4:], l_full[-4:], rtol=1e-4)


@pytest.mark.slow
def test_abft_protected_training_matches_baseline():
    """ABFT checksum columns must not change the math (checksum mode)."""
    l_off = train_run("qwen2-0.5b", smoke=True, steps=6, batch=4, seq=32,
                      log_every=100)
    l_on = train_run("qwen2-0.5b", smoke=True, steps=6, batch=4, seq=32,
                     abft_mode="checksum", log_every=100)
    np.testing.assert_allclose(l_on, l_off, rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_serving_with_abft_verify_deterministic():
    fin1, _ = serve_run("qwen2-0.5b", smoke=True, requests=2, slots=2,
                        prompt_len=12, gen=6, abft_mode="off", verbose=False)
    fin2, _ = serve_run("qwen2-0.5b", smoke=True, requests=2, slots=2,
                        prompt_len=12, gen=6, abft_mode="verify",
                        verbose=False)
    assert {r.rid: r.output for r in fin1} == \
        {r.rid: r.output for r in fin2}


@pytest.mark.slow
def test_serving_drill_corrects_in_flight():
    """The serving leg of the paper's claim: a bit flipped inside the
    decode-path collective is corrected on the fly — outputs identical to
    the clean run, event recorded."""
    from repro.ft.failures import SDCPlan

    clean, e0 = serve_run("qwen2-0.5b", smoke=True, requests=3, slots=2,
                          prompt_len=8, gen=5, abft_reduce="correct",
                          verbose=False)
    drilled, e1 = serve_run("qwen2-0.5b", smoke=True, requests=3, slots=2,
                            prompt_len=8, gen=5, abft_reduce="correct",
                            drill=SDCPlan(((2, 0, 1e4),)), verbose=False)
    assert e0.stats.detections == 0
    assert e1.stats.detections == 1 and e1.stats.corrections == 1
    assert {r.rid: r.output for r in clean} == \
        {r.rid: r.output for r in drilled}


@pytest.mark.parametrize("cli, args, expect", [
    ("repro.launch.serve",
     ["--requests", "2", "--slots", "2", "--prompt-len", "8", "--gen", "4",
      "--abft", "verify", "--reduce", "correct"],
     "protected reduce: detections=0 corrections=0"),
    ("repro.launch.train",
     ["--steps", "3", "--batch", "8", "--seq", "32", "--abft", "verify"],
     "[train] done"),
])
def test_cli_runs_at_smoke_size(tmp_path, cli, args, expect):
    """Both CLIs, as a user runs them, end to end on the CPU: ABFT-verified
    projections (plus the protected logits reduction when serving) on the
    1x1 mesh the launchers build through `dist.sharding.make_mesh`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    r = subprocess.run([sys.executable, "-m", cli, *args], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert expect in r.stdout, r.stdout
