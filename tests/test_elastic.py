"""Elastic re-mesh: reshard planning (single device) and the pod-loss
shrink/re-grow drills through `ft.runtime.ElasticRuntime` (subprocess:
multi-device)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.dist.sharding import make_mesh
from repro.configs.base import ShapeConfig, smoke_config
from repro.ckpt.disk import CheckpointManager
from repro.ckpt.elastic import reshard_restore
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.train.optimizer import AdamWConfig
from repro.train.step import StepOptions, build_train_step, init_state

cfg = smoke_config("qwen2-0.5b")
shape = ShapeConfig("t", 32, 8, "train")
opts = StepOptions(microbatches=1, remat=False)
dc = DataConfig(cfg.vocab_size, 32, 8)

# "2-pod" mesh: (pod=2, data=2, model=2)
mesh2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
with jax.set_mesh(mesh2):
    fn, in_sh, out_sh = build_train_step(cfg, mesh2, shape,
                                         AdamWConfig(total_steps=10), opts)
    jit_fn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    state = jax.device_put(init_state(jax.random.PRNGKey(0), cfg, opts, mesh2),
                           in_sh[0])
    for i in range(2):
        batch = jax.device_put({k: jnp.asarray(v) for k, v in
                                synthetic_batch(dc, i).items()}, in_sh[1])
        state, m = jit_fn(state, batch)
    loss2pod = float(m["loss"])

with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(2, state, blocking=True)

    # pod lost -> survivors form a (data=2, model=2) mesh
    mesh1 = make_mesh((2, 2), ("data", "model"))
    with jax.set_mesh(mesh1):
        like = jax.eval_shape(lambda: state)
        state1 = reshard_restore(mgr, 2, like, mesh1, opts, cfg)
        fn1, in_sh1, out_sh1 = build_train_step(cfg, mesh1, shape,
                                                AdamWConfig(total_steps=10), opts)
        jit1 = jax.jit(fn1, in_shardings=in_sh1, out_shardings=out_sh1)
        state1 = jax.device_put(state1, in_sh1[0])
        batch = jax.device_put({k: jnp.asarray(v) for k, v in
                                synthetic_batch(dc, 2).items()}, in_sh1[1])
        state1, m1 = jit1(state1, batch)
        assert np.isfinite(float(m1["loss"]))

        # the resumed step must equal the step the 2-pod mesh would take
        print("resumed-on-survivors loss:", float(m1["loss"]))
print("ELASTIC_OK")
"""

# The full runtime drill: 2x2x2 -> (2,2) shrink at step 3 through the disk
# rung (a pod's worth of shards exceeds diskless capacity), five post-shrink
# parity steps, re-grow at step 8.  The drill itself runs the
# survivor-mesh-from-scratch reference and reports parity.
DRILL_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.launch.train import run_elastic_drill
rep = run_elastic_drill("qwen2-0.5b", steps=10, kill_pod_at=3, regrow_at=8,
                        batch=8, seq=32, mesh_shape=(2, 2, 2), verbose=False)
print("REPORT::" + json.dumps(rep))
"""

# Rung 3a: on a (pod=2, data=1, model=1) drill the dead pod is ONE diskless
# shard (fits f=1), so the shrink restores from the in-memory checksum state,
# not disk.
DRILL_3A_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from repro.launch.train import run_elastic_drill
rep = run_elastic_drill("qwen2-0.5b", steps=5, kill_pod_at=2, regrow_at=None,
                        batch=4, seq=32, mesh_shape=(2, 1, 1), verbose=False)
print("REPORT::" + json.dumps(rep))
"""


def _run(script, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    return r


def _report(r):
    for line in r.stdout.splitlines():
        if line.startswith("REPORT::"):
            return json.loads(line[len("REPORT::"):])
    raise AssertionError(
        f"no REPORT in\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}")


@pytest.mark.slow
def test_elastic_pod_loss_restore():
    r = _run(SCRIPT)
    assert "ELASTIC_OK" in r.stdout, \
        f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"


@pytest.mark.slow
def test_elastic_drill_shrink_regrow_parity():
    """The ROADMAP acceptance drill: shrink -> resume -> re-grow with
    bit-identical restored params and step-for-step loss parity vs the
    survivor-mesh-from-scratch reference."""
    rep = _report(_run(DRILL_SCRIPT))
    parity = rep["parity"]
    assert parity["params_bitwise_equal"] is True
    assert parity["steps_compared"] >= 5          # five post-shrink steps
    assert parity["max_abs_loss_diff"] == 0.0     # step-for-step parity
    assert parity["loss_parity"] is True
    # shrink went through the disk rung (pod loss > diskless capacity) and
    # the placement diff is populated
    assert rep["shrink"]["restore_path"] == "disk"
    assert rep["shrink"]["bytes_total"] > 0
    assert rep["shrink"]["n_respecced"] > 0       # ZeRO dims moved
    assert rep["shrink"]["compile_s"] > 0.0       # survivor mesh recompiled
    # re-grow reused the generation-0 executable (no recompile)
    assert rep["regrow"]["reused_executable"] is True
    assert rep["regrow"]["compile_s"] == 0.0
    assert rep["regrow"]["rollback_step"] is None  # nothing lost on grow
    # post-regrow steps ran on the full mesh and stayed finite
    assert rep["recoveries"]["elastic"] == 2
    assert all(np.isfinite(v) for v in rep["losses"].values())


@pytest.mark.slow
def test_elastic_drill_diskless_rung_3a():
    """A pod loss that FITS the checksum capacity shrinks without disk:
    the diskless state is recovered + re-keyed for the survivor extent.
    The checksum recovery is a float SOLVE, so parity vs the disk-restored
    reference is near-exact (quantified), not bit-exact."""
    rep = _report(_run(DRILL_3A_SCRIPT))
    assert rep["shrink"]["restore_path"] == "diskless"
    parity = rep["parity"]
    assert parity["steps_compared"] >= 3
    assert parity["params_max_abs_diff"] < 1e-4
    assert parity["max_abs_loss_diff"] < 1e-3
    assert rep["recoveries"]["elastic"] == 1
