"""SDC drills through the distributed serving engine: a bit flipped inside
the decode path's cross-shard logits reduction must be detected, located
and corrected IN-FLIGHT, with slot outputs bit-identical to the clean run.

The multi-device drill runs in a subprocess (the main pytest process keeps
1 device, the conftest invariant); the clean-path regression and the stats
accounting run in-process on the engine's default 1-device mesh.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.base import smoke_config
from repro.ft.failures import SDCInjector, SDCPlan
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine

DRILL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.dist.sharding import make_mesh
from repro.configs.base import smoke_config
from repro.ft.failures import SDCInjector, SDCPlan
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine

mesh = make_mesh((4, 2), ("data", "model"))
cfg = smoke_config("qwen2-0.5b")
params = tf.init_params(jax.random.PRNGKey(0), cfg)
rs = np.random.RandomState(0)
prompts = [rs.randint(0, cfg.vocab_size, 8).tolist() for _ in range(4)]

def drive(sdc=None):
    eng = ServeEngine(cfg, params, slots=4, max_len=48, mesh=mesh,
                      abft_reduce="correct", sdc=sdc)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    fin = eng.run()
    return {r.rid: r.output for r in fin}, eng.stats

clean, s0 = drive()
assert s0.detections == 0 and s0.corrections == 0, s0
# two drills: one on each model shard, decode steps 1 and 3
drilled, s1 = drive(SDCInjector(SDCPlan(((1, 1, 1e4), (3, 0, -3e4)))))
assert s1.detections == 2 and s1.corrections == 2, s1
assert len(s1.events) == 2
for ev in s1.events:
    assert ev.detected and ev.corrected, ev
    assert ev.row >= 0 and ev.col >= 0, ev       # located, not just seen
assert drilled == clean, (drilled, clean)        # bit-identical slot outputs
print("SERVE_DRILL_DIST_OK")
"""


def _run(script: str, marker: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=560)
    assert marker in r.stdout, f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"


@pytest.mark.slow
def test_distributed_serve_drill_corrects_in_flight():
    """Bit flip injected into one model shard's contribution DURING the
    decode logits collective on a 4x2 mesh: detected, located, corrected;
    final slot outputs bit-identical to the clean run."""
    _run(DRILL_SCRIPT, "SERVE_DRILL_DIST_OK")


@pytest.mark.slow
def test_clean_protected_engine_reports_zero_detections():
    """Clean-path regression: the protected reduction must never
    false-positive — EngineStats reports zero detections and outputs match
    the unprotected engine (1-device mesh: psum association identical)."""
    cfg = smoke_config("qwen2-0.5b")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)

    def drive(**kw):
        eng = ServeEngine(cfg, params, slots=2, max_len=48, **kw)
        for i in range(3):
            eng.submit(Request(rid=i, prompt=[5 + i, 6, 7],
                               max_new_tokens=4))
        return {r.rid: r.output for r in eng.run()}, eng.stats

    base, _ = drive()
    prot, s = drive(abft_reduce="correct")
    assert s.detections == 0 and s.corrections == 0
    assert not s.events
    assert prot == base
    # per-step accounting is populated
    assert s.decode_steps == len(s.decode_step_s) > 0
    assert s.prefills == 3
    assert len(s.ttft_s) == 3 and all(t > 0 for t in s.ttft_s)


@pytest.mark.slow
def test_engine_warm_and_reset_reuse_compiled_programs():
    """`warm()` compiles prefill+decode (+drill variant) off the clock and
    `reset()` clears state/stats without dropping the compiled programs —
    a drilled run after warm() must behave exactly like a cold one."""
    cfg = smoke_config("qwen2-0.5b")
    params = tf.init_params(jax.random.PRNGKey(1), cfg)
    sdc = SDCInjector(SDCPlan(((1, 0, 1e4),)))
    eng = ServeEngine(cfg, params, slots=2, max_len=48,
                      abft_reduce="correct", sdc=sdc)
    eng.warm(prompt_len=8)
    assert eng.stats.decode_steps == 0          # stats reset after warm
    assert not sdc._fired                       # warm-up never fires drills
    for i in range(2):
        eng.submit(Request(rid=i, prompt=[1, 2, 3, 4], max_new_tokens=4))
    fin = eng.run()
    assert len(fin) == 2
    assert eng.stats.detections == 1 and eng.stats.corrections == 1
    ev = eng.stats.events[0]
    assert ev.step == 1 and ev.detected and ev.corrected
