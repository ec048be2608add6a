"""The paper's §4.3 stress test: an infinite(-ish) loop of encode -> multiply
-> random kill -> residual check.

"During the execution, a process killer is activated.  This process killer
kills randomly in time and in the location any process in the application.
Our application has successfully returned from tens of such failures."

Here the killer strikes a random device at a random SUMMA step each
iteration (sometimes a bit-flip instead), and every result must pass the
paper's residual check  ||Cx - A(Bx)|| / (n eps ||C|| ||x||) << threshold.

Run:  PYTHONPATH=src python examples/abft_stress.py [--iters 20]
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as core
from repro.dist.sharding import make_mesh


def residual_check(C, A, B, x):
    n = C.shape[0]
    eps = np.finfo(np.float32).eps
    lhs = jnp.linalg.norm(C @ x - A @ (B @ x))
    scale = n * eps * jnp.linalg.norm(C, "fro") * jnp.linalg.norm(x)
    return float(lhs / scale)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--grid", type=int, default=4)
    ap.add_argument("--block", type=int, default=32)
    args = ap.parse_args()

    g, nb = args.grid, args.block
    pr = g - 1
    n = pr * nb
    mesh = make_mesh((g, g), ("rows", "cols"))
    spec = core.make_spec(1, pr, pr)
    rs = np.random.RandomState(0)
    failures = 0
    flips = 0
    for it in range(args.iters):
        # fresh data each loop (paper: initialize, checkpoint, multiply, check)
        A = jnp.asarray(rs.standard_normal((n, g * nb)), jnp.float32)
        B = jnp.asarray(rs.standard_normal((g * nb, n)), jnp.float32)
        a_enc, b_enc = core.encode_operands(A, B, spec)

        # the process killer: random in time and location — occasionally it
        # takes out SEVERAL devices in the same instant
        kind = rs.randint(4)
        failure = bitflip = None
        if kind == 0:
            failure = core.FailureEvent(step=int(rs.randint(0, g)),
                                        row=int(rs.randint(0, g)),
                                        col=int(rs.randint(0, g)))
            failures += 1
        elif kind == 1:
            # two simultaneous losses on distinct rows+cols (f=1 capacity)
            r1, r2 = rs.choice(g, 2, replace=False)
            c1, c2 = rs.choice(g, 2, replace=False)
            failure = core.MultiFailureEvent(
                step=int(rs.randint(0, g)),
                devices=((int(r1), int(c1)), (int(r2), int(c2))))
            failure.check(1)
            failures += 2
        elif kind == 2:
            bitflip = core.BitflipEvent(step=int(rs.randint(0, g)),
                                        row=int(rs.randint(0, pr)),
                                        col=int(rs.randint(0, pr)),
                                        delta=float(10 ** rs.randint(2, 6)))
            flips += 1
        c_enc = core.abft_summa(a_enc, b_enc, mesh, spec=spec,
                                failure=failure, bitflip=bitflip)
        if bitflip is not None:
            c_enc, _, _ = core.locate_and_correct(c_enc, spec)
        C = core.strip(c_enc, nb, nb)
        x = jnp.asarray(rs.standard_normal((n,)), jnp.float32)
        r = residual_check(C, A, B, x)
        status = "kill" if failure else ("flip" if bitflip else "clean")
        assert r < 100.0, f"iteration {it} failed residual check: {r}"
        print(f"iter {it:3d} [{status:5s}] residual = {r:8.3f}  OK")
    print(f"\nsurvived {failures} process kills and {flips} bit-flips; "
          f"all {args.iters} residual checks passed")


if __name__ == "__main__":
    main()
