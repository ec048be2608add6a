"""Distributed ABFT SUMMA — multi-device assertions run in a subprocess so
the main pytest process keeps a single CPU device (see conftest note)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=25"
import numpy as np, jax, jax.numpy as jnp
import repro.core as core
from repro.dist.sharding import make_mesh

failures = []

def check(name, err, tol=1e-3):
    ok = err < tol
    print(f"{name}: err={err:.2e} {'OK' if ok else 'FAIL'}")
    if not ok:
        failures.append(name)

rs = np.random.RandomState(0)
for grid, f in [(4, 1), (5, 2)]:
    pr = grid - f
    mb = 8
    mesh = make_mesh((grid, grid), ("rows", "cols"))
    spec = core.make_spec(f, pr, pr)
    A = jnp.asarray(rs.standard_normal((pr * mb, grid * mb)), jnp.float32)
    B = jnp.asarray(rs.standard_normal((grid * mb, pr * mb)), jnp.float32)
    a_enc, b_enc = core.encode_operands(A, B, spec)
    ext = f * mb

    # plain SUMMA baseline (PBLAS analogue)
    c_plain = core.summa(A[:, :], B[:, :], mesh) if f == 0 else None

    c0 = core.abft_summa(a_enc, b_enc, mesh, spec=spec)
    check(f"grid{grid} f{f} nofail",
          float(jnp.max(jnp.abs(core.strip(c0, ext, ext) - A @ B))))
    assert bool(core.verify(c0, spec).consistent)

    # failures at every step x a few devices
    for step in range(grid):
        for (r, c) in [(0, 0), (1, 2), (grid - 1, 1), (2, grid - 1)]:
            ev = core.FailureEvent(step=step, row=r, col=c)
            cX = core.abft_summa(a_enc, b_enc, mesh, spec=spec, failure=ev)
            check(f"grid{grid} f{f} fail@{step}/{r},{c}",
                  float(jnp.max(jnp.abs(core.strip(cX, ext, ext) - A @ B))))

    # bit-flip + distributed verify + host correct
    bf = core.BitflipEvent(step=1, row=0, col=1, delta=1e4)
    cB = core.abft_summa(a_enc, b_enc, mesh, spec=spec, bitflip=bf)
    assert not bool(core.verify(cB, spec).consistent)
    fixed, was, _ = core.locate_and_correct(cB, spec)
    check(f"grid{grid} f{f} flipfix",
          float(jnp.max(jnp.abs(core.strip(fixed, ext, ext) - A @ B))))

# simultaneous multi-device failures (f=2 grid from the loop above)
grid, f = 5, 2
pr, mb = grid - f, 8
mesh = make_mesh((grid, grid), ("rows", "cols"))
spec = core.make_spec(f, pr, pr)
A = jnp.asarray(rs.standard_normal((pr*mb, grid*mb)), jnp.float32)
B = jnp.asarray(rs.standard_normal((grid*mb, pr*mb)), jnp.float32)
a_enc, b_enc = core.encode_operands(A, B, spec)
ext = f * mb
for devices in [((0, 0), (1, 1)), ((0, 2), (2, 2)), ((1, 0), (1, 3)),
                ((0, 0), (1, 1), (2, 2)), ((3, 1), (0, 1))]:
    ev = core.MultiFailureEvent(step=2, devices=devices)
    ev.check(f)
    cX = core.abft_summa(a_enc, b_enc, mesh, spec=spec, failure=ev)
    check(f"multi{devices}",
          float(jnp.max(jnp.abs(core.strip(cX, ext, ext) - A @ B))))
try:
    core.MultiFailureEvent(2, ((0, 0), (1, 0), (2, 0))).check(f)
    failures.append("over-capacity not rejected")
except ValueError:
    pass

# plain (non-FT) SUMMA == matmul
mesh = make_mesh((4, 4), ("rows", "cols"))
A = jnp.asarray(rs.standard_normal((32, 32)), jnp.float32)
B = jnp.asarray(rs.standard_normal((32, 32)), jnp.float32)
check("plain summa", float(jnp.max(jnp.abs(core.summa(A, B, mesh) - A @ B))))

assert not failures, failures
print("ALL_SUMMA_OK")
"""


@pytest.mark.slow
def test_distributed_summa_all_cases(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    # ~40 distinct failure configurations, each a fresh shard_map
    # trace+compile (~10s on a CPU host mesh) — budget accordingly
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1500)
    assert "ALL_SUMMA_OK" in r.stdout, f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"


FUSED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
import repro.core as core
from repro.dist.sharding import make_mesh

failures = []

def check(name, err, tol=1e-3):
    ok = err < tol
    print(f"{name}: err={err:.2e} {'OK' if ok else 'FAIL'}")
    if not ok:
        failures.append(name)

# MXU-tileable local blocks (mb=128) so the fused Pallas rank-kb update
# applies; interpret mode on this CPU host.
rs = np.random.RandomState(0)
grid, f, mb = 2, 1, 128
pr = grid - f
mesh = make_mesh((grid, grid), ("rows", "cols"))
spec = core.make_spec(f, pr, pr)
A = jnp.asarray(rs.standard_normal((pr * mb, grid * mb)), jnp.float32)
B = jnp.asarray(rs.standard_normal((grid * mb, pr * mb)), jnp.float32)
a_enc, b_enc = core.encode_operands(A, B, spec)
ext = f * mb

c0 = core.abft_summa(a_enc, b_enc, mesh, spec=spec, local_update="pallas")
check("fused nofail", float(jnp.max(jnp.abs(core.strip(c0, ext, ext) - A @ B))))
assert bool(core.verify(c0, spec).consistent)

# mid-loop bit-flip: the NEXT fused step's verify/correct prologue repairs
# it in-kernel, so the result is exact AND already checksum-consistent
# (no host-side locate_and_correct needed, unlike the jnp local update).
bf = core.BitflipEvent(step=1, row=0, col=1, delta=1e4)
cB = core.abft_summa(a_enc, b_enc, mesh, spec=spec, bitflip=bf,
                     local_update="pallas")
check("fused flip", float(jnp.max(jnp.abs(core.strip(cB, ext, ext) - A @ B))))
assert bool(core.verify(cB, spec).consistent), "in-kernel scrub missed flip"

# flip after the LAST accumulate: caught by the post-loop state scrub
bf2 = core.BitflipEvent(step=grid, row=1, col=0, delta=-3e3)
cB2 = core.abft_summa(a_enc, b_enc, mesh, spec=spec, bitflip=bf2,
                      local_update="pallas")
check("fused last-flip", float(jnp.max(jnp.abs(core.strip(cB2, ext, ext) - A @ B))))
assert bool(core.verify(cB2, spec).consistent)

# device failure mid-loop: T_checksum recovery + kernel-state refresh
ev = core.FailureEvent(step=1, row=0, col=0)
cX = core.abft_summa(a_enc, b_enc, mesh, spec=spec, failure=ev,
                     local_update="pallas")
check("fused fail@1", float(jnp.max(jnp.abs(core.strip(cX, ext, ext) - A @ B))))

assert not failures, failures
print("ALL_FUSED_SUMMA_OK")
"""


def test_distributed_summa_fused_local_update():
    """abft_summa routed through the fused Pallas rank-kb update (interpret
    mode on CPU): clean run, in-kernel bit-flip scrub, post-loop scrub, and
    failure recovery with kernel-state refresh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", FUSED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "ALL_FUSED_SUMMA_OK" in r.stdout, \
        f"\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
