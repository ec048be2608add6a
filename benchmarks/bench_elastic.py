"""Elastic pod-loss drill accounting — the BENCH_PR4 rows.

Runs the `launch.train` shrink/re-grow drill in a SUBPROCESS on 8 HOST
(CPU) devices (`JAX_PLATFORMS=cpu` plus
`--xla_force_host_platform_device_count`, set before jax imports): the
bench process itself holds the accelerator, if there is one, and a child
must not reach for it.  Every row is labelled ``devices=8xcpu-host`` — a
host-device drill, not a chip measurement.  It reports what the elastic
transition cost:

    elastic/shrink_reshard_wall   us to restore + re-place the state onto
                                  the survivor mesh (bytes moved derived)
    elastic/shrink_recompile      us to build+compile the survivor step
    elastic/regrow_reshard_wall   us to spread the live state back out
                                  (executable reuse derived)
    elastic/steps_to_parity       post-shrink steps compared against the
                                  survivor-mesh-from-scratch reference
                                  (max |dloss| + bit-identity derived)
"""
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

MESH = (2, 2, 2)
STEPS, KILL_AT, REGROW_AT = 8, 3, 6


def _drill_report() -> dict:
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={2 * 2 * 2}")
    with tempfile.TemporaryDirectory() as d:
        out = Path(d) / "drill.json"
        cmd = [sys.executable, "-m", "repro.launch.train",
               "--kill-pod-at-step", str(KILL_AT),
               "--regrow-at-step", str(REGROW_AT),
               "--steps", str(STEPS), "--batch", "8", "--seq", "32",
               "--drill-mesh", "x".join(map(str, MESH)),
               "--drill-json", str(out)]
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=540)
        if r.returncode != 0 or not out.exists():
            raise RuntimeError(
                f"elastic drill failed ({r.returncode}):\n"
                f"STDOUT:{r.stdout[-2000:]}\nSTDERR:{r.stderr[-2000:]}")
        return json.loads(out.read_text())


def run():
    rep = _drill_report()
    shrink, regrow, parity = rep["shrink"], rep["regrow"], rep["parity"]
    lines = [
        ("elastic/shrink_reshard_wall",
         f"{shrink['reshard_wall_s']*1e6:.0f}",
         f"bytes_moved={shrink['bytes_total']} "
         f"bytes_respecced={shrink['bytes_respecced']} "
         f"leaves={shrink['n_leaves']} respecced={shrink['n_respecced']} "
         f"path={shrink['restore_path']} "
         f"mesh={rep['mesh']}->{list(rep['survivor_mesh'].values())}"),
        ("elastic/shrink_recompile",
         f"{shrink['compile_s']*1e6:.0f}",
         f"build_s={shrink['build_s']:.2f} "
         f"rollback_step={shrink['rollback_step']}"),
        ("elastic/regrow_reshard_wall",
         f"{regrow['reshard_wall_s']*1e6:.0f}",
         f"reused_executable={regrow['reused_executable']} "
         f"recompile_us={regrow['compile_s']*1e6:.0f}"),
        ("elastic/steps_to_parity",
         f"{parity['steps_compared']}",
         f"max_abs_loss_diff={parity['max_abs_loss_diff']} "
         f"params_bitwise_equal={parity['params_bitwise_equal']} "
         f"window={parity['window']}"),
    ]
    return [(name, us, f"{derived} devices=8xcpu-host")
            for name, us, derived in lines]
