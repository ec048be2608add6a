"""Continuous-batching serving with ABFT-verified projections.

Eight requests stream through a 2-slot engine on a paged KV cache: slots
retire and re-admit independently (per-slot positions, pages freed and
reallocated), every projection carries Huang-Abraham checksum columns
(silent-corruption detection while serving).

Run:  PYTHONPATH=src python examples/continuous_batching.py
"""
import time

import jax
import numpy as np

from repro.configs.base import smoke_config
from repro.models import transformer as tf
from repro.serve.engine import PagedServeEngine, Request


def main():
    cfg = smoke_config("qwen2-0.5b")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    engine = PagedServeEngine(cfg, params, slots=2, max_len=64,
                              abft_mode="verify")

    rs = np.random.RandomState(0)
    t0 = time.time()
    for i in range(8):
        prompt = rs.randint(0, cfg.vocab_size, rs.randint(4, 12)).tolist()
        engine.submit(Request(rid=i, prompt=prompt, max_new_tokens=6))
    finished = engine.run()
    dt = time.time() - t0
    toks = sum(len(r.output) for r in finished)
    print(f"[engine] {len(finished)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s) with ABFT verify on")
    for r in sorted(finished, key=lambda r: r.rid):
        print(f"  rid={r.rid}: {r.output}")


if __name__ == "__main__":
    main()
