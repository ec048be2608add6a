"""Measured end-to-end train/serve step timings on CPU (reduced configs) —
the live-system analogue of the paper's experiments: ABFT on vs off through
the full training stack, plus diskless-encode cost (the 'checkpoint' op the
paper hides behind compute)."""
import time

import numpy as np


def _wall(fn, *args, reps=3):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run():
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig, smoke_config
    from repro.data.pipeline import DataConfig, synthetic_batch
    from repro.dist.sharding import make_mesh
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import StepOptions, build_train_step, init_state
    from repro.ckpt.diskless import DisklessCheckpoint

    lines = []
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("b", 128, 8, "train")
    for arch in ("qwen2-0.5b", "qwen3-moe-30b-a3b", "xlstm-350m"):
        cfg = smoke_config(arch)
        dc = DataConfig(cfg.vocab_size, 128, 8)
        batch = {k: jnp.asarray(v) for k, v in synthetic_batch(dc, 0).items()}
        times = {}
        with jax.set_mesh(mesh):
            for mode in ("off", "checksum"):
                opts = StepOptions(abft_mode=mode, remat=False)
                fn, in_sh, _ = build_train_step(
                    cfg, mesh, shape, AdamWConfig(total_steps=10), opts)
                state = init_state(jax.random.PRNGKey(0), cfg, opts)
                jit_fn = jax.jit(fn, in_shardings=in_sh)
                times[mode] = _wall(lambda s, b: jit_fn(s, b)[1]["loss"],
                                    state, batch)
        ov = 100 * times["checksum"] / times["off"]
        lines.append((f"train_step/{arch}", f"{times['off']*1e6:.0f}",
                      f"abft_checksum_overhead={ov:.1f}%"))

    # diskless encode cost vs a train step (the paper's hidden checkpoint)
    cfg = smoke_config("qwen2-0.5b")
    opts = StepOptions(remat=False)
    state = init_state(jax.random.PRNGKey(0), cfg, opts)
    import jax as _jax
    stacked = _jax.tree.map(
        lambda x: x.reshape((4, x.shape[0] // 4) + x.shape[1:])
        if x.ndim and x.shape[0] % 4 == 0 else x, state["params"])
    dcp = DisklessCheckpoint(4, f=1)
    t_enc = _wall(lambda s: _jax.tree.leaves(dcp.encode(s))[0], stacked)
    lines.append(("diskless_encode/qwen2-0.5b-smoke", f"{t_enc*1e6:.0f}",
                  f"bytes={sum(x.nbytes for x in _jax.tree.leaves(stacked))}"))

    # the telemetry bus's own cost on the step path: the identical jitted
    # step driven through the ElasticRuntime-style producer calls
    # (set_step + span + counter) with the bus on vs off.  CI's obs-smoke
    # job gates the delta <2% — the "cheap when idle" design constraint
    # of repro/obs/trace.py, measured not assumed.
    from repro import obs

    cfg = smoke_config("qwen2-0.5b")
    dc = DataConfig(cfg.vocab_size, 128, 8)
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(dc, 0).items()}
    opts = StepOptions(abft_mode="off", remat=False)
    with jax.set_mesh(mesh):
        fn, in_sh, _ = build_train_step(cfg, mesh, shape,
                                        AdamWConfig(total_steps=10), opts)
        state = init_state(jax.random.PRNGKey(0), cfg, opts)
        jit_fn = jax.jit(fn, in_shardings=in_sh)
        clock = [0]

        def stepped(s, b):
            clock[0] += 1
            obs.set_step(clock[0])
            with obs.span("train/step", step=clock[0]):
                out = jit_fn(s, b)[1]["loss"]
            obs.counter("repro_train_steps_total").inc()
            return out

        prev = obs.enabled()
        obs.enable(False)
        t_off = _wall(stepped, state, batch, reps=10)
        obs.enable(True)
        t_on = _wall(stepped, state, batch, reps=10)
        obs.enable(prev)
    ov = 100 * (t_on / t_off - 1.0)
    lines.append(("train_step_obs/qwen2-0.5b-smoke", f"{t_on*1e6:.0f}",
                  f"obs_bus_overhead={ov:+.2f}% "
                  f"(off={t_off*1e6:.0f}us, budget <2%)"))

    # at-rest scrub verify: the read side of the scrubber re-runs the encode
    # against the held checksums.  Off the step critical path (it runs
    # between steps, against state the step doesn't mutate), so the row is
    # the absolute wall, not an overhead % of the step.
    dcp.encode(stacked, step=0)
    t_ver = _wall(lambda s: dcp.verify(s)[0], stacked)
    lines.append(("scrub_verify/qwen2-0.5b-smoke", f"{t_ver*1e6:.0f}",
                  f"encode_ratio={t_ver/t_enc:.2f}x "
                  "(off the step critical path)"))
    return lines
