"""Serving driver: the fault-tolerant continuous-batching engine as a CLI.

Drives `serve.PagedServeEngine` — slot-scheduled prefill+decode over a paged
KV cache with ABFT-verified projections (``--abft verify``), a
checksum-protected decode-path logits reduction (``--reduce
verify|correct``) and optional SDC drills that flip a bit inside the decode
collective mid-flight (``--drill-step/shard/delta``).
Single-host it serves a reduced config; with ``--mesh RxM`` the compiled
programs shard over a (data=R, model=M) `repro.dist` mesh (spawn fake CPU
devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

Usage (CPU examples):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --requests 6 --slots 2 --gen 16 --abft verify
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --reduce correct --drill-step 3 --drill-delta 1e4
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax
import numpy as np

from repro.configs.base import get_config, smoke_config
from repro.dist.sharding import make_mesh
from repro.ft.failures import SDCInjector, SDCPlan
from repro.launch import compile_cache
from repro.models import transformer as tf
from repro.serve.engine import PagedServeEngine, Request


def run(arch: str, *, smoke: bool = True, requests: int = 6, slots: int = 2,
        prompt_len: int = 8, gen: int = 16, abft_mode: str = "off",
        abft_reduce: str = "off", mesh_shape: Optional[tuple] = None,
        drill: Optional[SDCPlan] = None, seed: int = 0, verbose: bool = True):
    """Build a (possibly drilled) engine, serve `requests` requests, return
    ``(finished_requests, engine)`` — the engine exposes `.stats`."""
    cfg = smoke_config(arch) if smoke else get_config(arch)
    if cfg.n_enc_layers or cfg.n_img_tokens:
        raise ValueError(
            f"{arch} needs encoder frames / image embeddings, which the "
            "continuous-batching engine does not feed yet — serve a "
            "decoder-only text arch (e.g. qwen2-0.5b), or drive "
            "train.step.build_serve_step directly for these archs")
    params = tf.init_params(jax.random.PRNGKey(seed), cfg)
    mesh = None
    if mesh_shape is not None:
        mesh = make_mesh(mesh_shape, ("data", "model"))
    page = 8
    engine = PagedServeEngine(
        cfg, params, slots=slots, page_size=page,
        max_len=-(-(prompt_len + gen + 8) // page) * page,   # whole pages
        abft_mode=abft_mode, abft_reduce=abft_reduce, mesh=mesh,
        sdc=SDCInjector(drill) if drill is not None else None)
    engine.warm(prompt_len=prompt_len)
    rs = np.random.RandomState(seed)
    for i in range(requests):
        engine.submit(Request(
            rid=i, prompt=rs.randint(0, cfg.vocab_size, prompt_len).tolist(),
            max_new_tokens=gen))
    finished = engine.run()
    if verbose:
        s = engine.stats.summary()
        print(f"[serve] {arch}: {len(finished)} requests, "
              f"{s['decode_steps']} decode steps "
              f"(prefill {s['prefill_s']*1e3:.1f}ms, "
              f"decode {s['decode_s']*1e3:.1f}ms), "
              f"ttft {s['ttft_ms']:.1f}ms, {s['tok_per_s']:.1f} tok/s/seq")
        if abft_reduce != "off":
            print(f"[serve] protected reduce: detections={s['detections']} "
                  f"corrections={s['corrections']} "
                  f"recovery_latency={s['recovery_latency_ms']:.2f}ms")
        for ev in engine.stats.events:
            print(f"[serve] SDC drill @step {ev.step}: shard {ev.shard} "
                  f"delta {ev.delta:+.3g} -> detected={ev.detected} "
                  f"corrected={ev.corrected} located=({ev.row},{ev.col})")
        sample = finished[0].output[:16] if finished else []
        print(f"[serve] sample generation ids[0,:16]: {sample}")
    return finished, engine


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="serve the published config instead of the "
                         "reduced smoke config")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--abft", default="off",
                    choices=["off", "checksum", "verify", "correct"])
    ap.add_argument("--reduce", default="off",
                    choices=["off", "verify", "correct"],
                    help="checksum-protect the decode-path logits reduction")
    ap.add_argument("--mesh", default=None, metavar="RxM",
                    help="shard over a (data=R, model=M) mesh, e.g. 4x2")
    ap.add_argument("--drill-step", type=int, default=None,
                    help="engine decode step to fire an SDC drill at")
    ap.add_argument("--drill-shard", type=int, default=0,
                    help="model-axis shard whose contribution corrupts")
    ap.add_argument("--drill-delta", type=float, default=1e4)
    args = ap.parse_args()
    mesh_shape = (tuple(int(v) for v in args.mesh.split("x"))
                  if args.mesh else None)
    drill = None
    if args.drill_step is not None:
        if args.reduce == "off":
            ap.error("--drill-step needs --reduce verify|correct")
        drill = SDCPlan(((args.drill_step, args.drill_shard,
                          args.drill_delta),))
    run(args.arch, smoke=args.smoke, requests=args.requests, slots=args.slots,
        prompt_len=args.prompt_len, gen=args.gen, abft_mode=args.abft,
        abft_reduce=args.reduce, mesh_shape=mesh_shape, drill=drill)


if __name__ == "__main__":
    main()
