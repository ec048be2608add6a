"""Autotune-cache pre-warm CLI: measure ABFT-GEMM tilings once, persist.

Runs the measured autotuner (``kernels.autotune``) over a shape x dtype
grid and persists the winners to the on-disk cache, so that later runs —
serving engines, benches, CI — resolve plans with ZERO measurements.  The
warm/cold split is observable: ``--json`` reports the measurement counter,
and the CI ``autotune-smoke`` job asserts a second (warm) invocation
measures nothing.

On CPU the measurement backend is the XLA twin of the fused kernel (same
semantics; honest wall-clock of what this host actually runs); on TPU it
is the Pallas kernel itself.  Plans are keyed by
``{device}/{acc|one}/f{f}/{in_dtype}->{out_dtype}/{m}x{k}x{n}`` so a cache
warmed on one device kind never serves another.

Usage:
  # warm the default cache (~/.cache/repro/autotune.json) for the bench set
  PYTHONPATH=src python -m repro.launch.autotune --shapes bench

  # tiny smoke set into an explicit cache, machine-readable summary
  PYTHONPATH=src python -m repro.launch.autotune --shapes smoke \
      --cache /tmp/autotune.json --json /tmp/warm.json

  # custom shapes / dtypes
  PYTHONPATH=src python -m repro.launch.autotune \
      --shape 512x512x512 --shape 384x640x896 --dtypes float32,bfloat16
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.launch import compile_cache

SHAPE_SETS = {
    "smoke": [(256, 256, 256)],
    "bench": [(256, 256, 256), (256, 512, 384), (512, 512, 512),
              (1024, 1024, 1024)],
}
DTYPES = ("float32", "bfloat16", "int8")


def _parse_shape(s: str):
    try:
        m, k, n = (int(p) for p in s.lower().split("x"))
        return m, k, n
    except ValueError:
        raise SystemExit(f"bad --shape {s!r}: want MxKxN, e.g. 512x512x512")


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(
        description="pre-warm the ABFT-GEMM autotune cache")
    ap.add_argument("--shapes", choices=sorted(SHAPE_SETS), default=None,
                    help="named shape set")
    ap.add_argument("--shape", action="append", default=[],
                    metavar="MxKxN", help="explicit shape (repeatable)")
    ap.add_argument("--dtypes", default="float32,bfloat16,int8",
                    help="comma-separated input dtypes")
    ap.add_argument("--cache", default=None,
                    help="cache file (default: REPRO_AUTOTUNE_CACHE or "
                         "~/.cache/repro/autotune.json)")
    ap.add_argument("--top-k", type=int, default=4,
                    help="measured candidates per shape (model plan incl.)")
    ap.add_argument("--reps", type=int, default=2,
                    help="timed repetitions per candidate (best-of)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write a machine-readable warm summary")
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from repro.kernels import autotune as at

    shapes = [_parse_shape(s) for s in args.shape]
    if args.shapes:
        shapes += SHAPE_SETS[args.shapes]
    if not shapes:
        shapes = SHAPE_SETS["smoke"]
    dtypes = [d.strip() for d in args.dtypes.split(",") if d.strip()]
    for d in dtypes:
        if d not in DTYPES:
            raise SystemExit(f"unknown dtype {d!r}: pick from {DTYPES}")

    at.reset_stats()
    rows = []
    for (m, k, n) in shapes:
        for d in dtypes:
            in_dtype = jnp.dtype(d)
            out_dtype = jnp.int32 if d == "int8" else jnp.float32
            plan, info = at.autotune(
                m, k, n, in_dtype=in_dtype, out_dtype=out_dtype,
                top_k=args.top_k, reps=args.reps, cache=args.cache)
            blocks = f"{plan.bm}x{plan.bn}x{plan.bk}"
            rows.append(dict(key=info["key"], source=info["source"],
                             blocks=blocks, best_us=info.get("best_us"),
                             persisted=info.get("persisted", False)))
            print(f"{info['key']}: {info['source']} -> {blocks}"
                  + (f" ({info['best_us']:.0f}us)"
                     if info.get("best_us") is not None else ""))

    st = at.stats()
    summary = dict(device=at.device_kind(),
                   cache=str(args.cache or at.cache_path()),
                   measurements=st["measurements"],
                   cache_hits=st["cache_hits"], plans=rows)
    print(f"measurements={st['measurements']} cache_hits={st['cache_hits']} "
          f"cache={summary['cache']}", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(summary, fh, indent=1, sort_keys=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
