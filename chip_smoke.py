#!/usr/bin/env python3
"""Chip smoke: the main path once, on a TPU, at qwen2-0.5b's full width.

    python chip_smoke.py             # one chip: serve, then train
    python chip_smoke.py --chips 4   # four chips: FT-SUMMA on a 2x2 mesh and
                                     # serving on a (data=2, model=2) mesh

One process; the phases run in order and any failed check raises, so the
exit code is non-zero and no result line is printed.  It fails at once
when JAX finds no TPU: nothing here falls back to the CPU.

One chip (the published qwen2-0.5b: 24 layers, d 896, 14/2 heads, d_ff 4864,
vocab 151936, bf16; weights random from a seed):
  serve  4 requests, prompt 128, 16 new tokens, through
         `repro.launch.serve.run` with ABFT-verified projections and the
         checksum-corrected logits reduction.  Checks: the fused Pallas
         kernel was traced; the same requests with one SDC drill are
         detected and corrected with identical tokens.
  train  3 steps through `repro.launch.train.run` with ABFT verify; the
         losses must be finite.

Four chips (only the path that spans chips, and what it is compared with):
  summa  `abft_summa` on a 2x2 (rows, cols) mesh, 8192^2 encoded fp32, the
         fused Pallas local update on 4096^2 local blocks; clean, with one
         device failure, and with one bit flip — each against `jnp.dot` on
         one device at the same (default) precision.
  serve  `PagedServeEngine` on a (data=2, model=2) mesh with the corrected
         logits reduction and one drill, against the same requests on a
         one-device mesh (fp32 weights, so the two layouts' summation
         orders cannot flip an argmax).

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
Walls printed before it are smoke timings (compilation included), not
metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "qwen2-0.5b"
SEED = 0

# one chip
REQUESTS, SLOTS, PROMPT_LEN, GEN = 4, 4, 128, 16
DRILL = (3, 0, 1e4)                   # (decode step, model shard, delta)
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 256

# four chips
SUMMA_MB = 4096                       # local block edge on the 2x2 grid
# max |C - AB| / max |AB| against the same product at the same precision:
# clean, the two differ by fp32 summation order (~1e-7); a lost, misplaced
# or uncorrected block is off by 1e-1 or more
SUMMA_REL_TOL = 1e-3
SHARDED_DRILL = (3, 1, 1e4)           # drill the second model shard


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_check(chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"devices: {dev}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX reports platform {dev['platform']!r}")
    check(dev["count"] >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {dev['count']}")
    return dev


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def _streams(finished) -> dict:
    return {r.rid: list(r.output) for r in finished}


def _kernel_traces() -> dict:
    from repro import obs
    c = obs.counter("repro_kernel_traces_total")
    return {",".join(f"{k}={v}" for k, v in key): int(val)
            for key, val in c.samples()}


def phase_serve():
    from repro import obs
    from repro.ft.failures import SDCPlan
    from repro.launch import serve

    kw = dict(smoke=False, requests=REQUESTS, slots=SLOTS,
              prompt_len=PROMPT_LEN, gen=GEN, abft_mode="verify",
              abft_reduce="correct", seed=SEED)
    clean, eng = serve.run(ARCH, **kw)
    ref = _streams(clean)
    check(sorted(ref) == list(range(REQUESTS))
          and all(len(t) == GEN for t in ref.values()),
          f"clean serve: expected {REQUESTS} x {GEN} tokens, got "
          f"{ {k: len(v) for k, v in ref.items()} }")
    check(all(0 <= t < eng.cfg.vocab_size for v in ref.values() for t in v),
          "clean serve: token id out of vocabulary")
    check(eng.stats.detections == 0,
          f"clean serve: {eng.stats.detections} false detections")

    drilled, deng = serve.run(ARCH, drill=SDCPlan((DRILL,)), **kw)
    s = deng.stats
    check(s.detections == 1 and s.corrections == 1,
          f"drill: detections={s.detections} corrections={s.corrections}, "
          "expected 1 and 1")
    check(_streams(drilled) == ref,
          "drill: corrected token streams differ from the clean run")
    log(f"serve drill at decode step {DRILL[0]}: detected and corrected, "
        "tokens identical to the clean run")

    traces = _kernel_traces()
    log(f"kernel traces by backend: {traces}")
    check(obs.counter("repro_kernel_traces_total").value(
        op="abft_matmul", backend="pallas") > 0,
        "no projection traced the fused Pallas kernel (backend=pallas)")


def phase_train():
    from repro.launch import train
    losses = train.run(ARCH, smoke=False, steps=TRAIN_STEPS,
                       batch=TRAIN_BATCH, seq=TRAIN_SEQ, abft_mode="verify",
                       log_every=1, seed=SEED)
    check(len(losses) == TRAIN_STEPS
          and all(math.isfinite(x) for x in losses),
          f"train: losses {losses}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_summa():
    import jax
    import jax.numpy as jnp
    import repro.core as core
    from repro.dist.sharding import make_mesh

    grid, f, mb = 2, 1, SUMMA_MB
    pr = grid - f
    mesh = make_mesh((grid, grid), ("rows", "cols"))
    spec = core.make_spec(f, pr, pr)
    ka, kb = jax.random.split(jax.random.PRNGKey(SEED))
    a = jax.random.normal(ka, (pr * mb, grid * mb), jnp.float32)
    b = jax.random.normal(kb, (grid * mb, pr * mb), jnp.float32)
    one = jax.devices()[0]
    a1, b1 = jax.device_put(a, one), jax.device_put(b, one)
    # the reference runs at the chip's default fp32 matmul precision, as the
    # SUMMA local updates do (XLA and the Pallas kernel both round the
    # operands to bf16 for one MXU pass); the HIGHEST-precision product is
    # logged beside it
    want = jnp.dot(a1, b1)
    exact = jnp.dot(a1, b1, precision=jax.lax.Precision.HIGHEST)
    scale = float(jnp.max(jnp.abs(want)))
    a_enc, b_enc = core.encode_operands(a, b, spec)
    ext = f * mb
    runs = {
        "clean": {},
        "device failure": {"failure": core.FailureEvent(step=1, row=0,
                                                        col=1)},
        "bit flip": {"bitflip": core.BitflipEvent(step=1, row=1, col=0,
                                                  delta=1e4)},
    }
    for name, ev in runs.items():
        # "pallas": the fused local update or an error, never the jnp twin
        c = core.abft_summa(a_enc, b_enc, mesh, spec=spec,
                            local_update="pallas", **ev)
        check(len(c.sharding.device_set) == grid * grid,
              f"summa {name}: result spans {len(c.sharding.device_set)} "
              "devices")
        got = jax.device_put(core.strip(c, ext, ext), one)
        rel = float(jnp.max(jnp.abs(got - want))) / scale
        rel_exact = float(jnp.max(jnp.abs(got - exact))) / scale
        log(f"summa {name}: max|C - AB| / max|AB| = {rel!r} (default "
            f"precision), {rel_exact!r} (HIGHEST, not checked)")
        check(rel <= SUMMA_REL_TOL,
              f"summa {name}: relative error {rel} > {SUMMA_REL_TOL}")
        check(bool(core.verify(c, spec).consistent),
              f"summa {name}: result not checksum-consistent")
    log(f"kernel traces by backend: {_kernel_traces()}")


def phase_serve_sharded():
    import jax
    import numpy as np
    from repro.configs.base import get_config
    from repro.dist.sharding import make_mesh
    from repro.ft.failures import SDCInjector, SDCPlan
    from repro.models import transformer as tf
    from repro.serve.engine import PagedServeEngine, Request

    cfg = get_config(ARCH).scaled(dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(SEED), cfg)
    rs = np.random.RandomState(SEED)
    prompts = [rs.randint(0, cfg.vocab_size, PROMPT_LEN).tolist()
               for _ in range(REQUESTS)]

    def drive(mesh, sdc=None):
        eng = PagedServeEngine(cfg, params, slots=SLOTS,
                               max_len=PROMPT_LEN + GEN + 8, mesh=mesh,
                               abft_reduce="correct", sdc=sdc)
        eng.warm(prompt_len=PROMPT_LEN)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=GEN))
        return _streams(eng.run()), eng

    ref, _ = drive(make_mesh((1, 1), ("data", "model"),
                             devices=jax.devices()[:1]))
    got, eng = drive(make_mesh((2, 2), ("data", "model")),
                     SDCInjector(SDCPlan((SHARDED_DRILL,))))
    leaves = jax.tree.leaves(eng.params) + jax.tree.leaves(eng.kv.pools)
    devices = {d for x in leaves for d in x.sharding.device_set}
    check(len(devices) == 4, f"sharded serve spans {len(devices)} devices")
    check(any(not x.sharding.is_fully_replicated for x in leaves),
          "sharded serve: every array is replicated")
    s = eng.stats
    check(s.detections == 1 and s.corrections == 1,
          f"sharded drill: detections={s.detections} "
          f"corrections={s.corrections}, expected 1 and 1")
    check(got == ref,
          "sharded serve tokens differ from the one-device mesh's")
    log(f"sharded serve on {len(devices)} devices: drill corrected, tokens "
        "identical to the one-device mesh")


# ---------------------------------------------------------------------------


PHASES = {
    1: (("serve", phase_serve), ("train", phase_train)),
    4: (("summa 2x2", phase_summa), ("serve 2x2", phase_serve_sharded)),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="1: serve + train on one chip; 4: the paths "
                         "that span a 2x2 mesh")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")

    t0 = time.perf_counter()
    device = device_check(args.chips)
    for name, phase in PHASES[args.chips]:
        t = time.perf_counter()
        phase()
        log(f"phase {name}: passed, wall {time.perf_counter() - t:.1f} s "
            "(smoke timing incl. compilation, not a metric)")
        gc.collect()
    log(f"all phases passed, wall {time.perf_counter() - t0:.1f} s "
        "(smoke timing, not a metric)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
