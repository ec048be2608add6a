"""Quickstart: the paper's ABFT pipeline end-to-end in two minutes on CPU.

1. encode two matrices with Huang-Abraham block checksums,
2. multiply them with the distributed ABFT SUMMA (8 simulated devices),
3. kill a device mid-multiply -> in-flight recovery (no rollback),
4. flip a bit in the result -> detect / locate / correct,
5. run an ABFT-protected transformer projection (the LM integration).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as core
from repro.dist.sharding import make_mesh


def main():
    rs = np.random.RandomState(0)

    # --- 1. encode -----------------------------------------------------------
    # 4x4 device grid, f=1: data lives on the 3x3 sub-grid (paper: (p-1)^2 of
    # p^2 processes hold data, 2p-1 hold checksums).
    spec = core.make_spec(f=1, pr=3, pc=3)
    A = jnp.asarray(rs.standard_normal((96, 128)), jnp.float32)
    B = jnp.asarray(rs.standard_normal((128, 96)), jnp.float32)
    a_enc, b_enc = core.encode_operands(A, B, spec)
    print(f"encoded A: {A.shape} -> {a_enc.shape} (checksum block-rows)")

    # --- 2. distributed ABFT SUMMA ------------------------------------------
    mesh = make_mesh((4, 4), ("rows", "cols"))
    c_enc = core.abft_summa(a_enc, b_enc, mesh, spec=spec)
    err = float(jnp.max(jnp.abs(core.strip(c_enc, 32, 32) - A @ B)))
    print(f"SUMMA (no failure): max|C - AB| = {err:.2e}")

    # --- 3. kill a device mid-multiply --------------------------------------
    ev = core.FailureEvent(step=2, row=1, col=2)
    c_enc = core.abft_summa(a_enc, b_enc, mesh, spec=spec, failure=ev)
    err = float(jnp.max(jnp.abs(core.strip(c_enc, 32, 32) - A @ B)))
    print(f"SUMMA (device (1,2) died at step 2, recovered in-flight): "
          f"max err = {err:.2e}")

    # --- 4. bit-flip detect/locate/correct ----------------------------------
    flip = core.BitflipEvent(step=3, row=0, col=1, delta=1e3)
    c_bad = core.abft_summa(a_enc, b_enc, mesh, spec=spec, bitflip=flip)
    ok = bool(core.verify(c_bad, spec).consistent)
    fixed, was_corrupt, (r, c) = core.locate_and_correct(c_bad, spec)
    err = float(jnp.max(jnp.abs(core.strip(fixed, 32, 32) - A @ B)))
    print(f"bit-flip: consistent={ok}, located=({int(r)},{int(c)}), "
          f"corrected err = {err:.2e}")

    # --- 5. ABFT-protected LM projection -------------------------------------
    cfg = core.ABFTConfig(mode="correct", f=2)
    W = jnp.asarray(rs.standard_normal((256, 512)), jnp.float32)
    X = jnp.asarray(rs.standard_normal((8, 256)), jnp.float32)
    W_enc = core.encode_weight(W, cfg)
    Y, ok = core.abft_matmul(X, W_enc, cfg)
    print(f"protected projection: verified ok={bool(ok)}, "
          f"err = {float(jnp.max(jnp.abs(Y - X @ W))):.2e}")


if __name__ == "__main__":
    main()
