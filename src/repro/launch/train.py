"""End-to-end training driver + the pod-loss drill CLI.

Single-host (CPU) it trains a reduced config for real; on a pod the same
driver runs the full config — the mesh/topology is the only difference.
Integrates: data pipeline (prefetch + exact resume), AdamW + schedule,
remat/microbatching, ABFT-protected projections, diskless + disk
checkpointing, failure injection + recovery (the paper's stress test as a
flag), and resume.

Usage (CPU example):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 200 --batch 16 --seq 128 --inject-failures 3

Pod-loss drill (`ft.runtime.ElasticRuntime` end-to-end: shrink onto the
survivor mesh at step N, resume, re-grow at step M, then verify
step-for-step loss parity against a survivor-mesh-from-scratch restore;
needs enough host devices for the drill mesh):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
  python -m repro.launch.train --arch qwen2-0.5b --steps 10 --batch 8 \
      --seq 32 --drill-mesh 2x2x2 --kill-pod-at-step 4 --regrow-at-step 7 \
      --drill-json drill.json
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ShapeConfig, get_config, smoke_config
from repro.data.pipeline import DataConfig, DataPipeline
from repro.data.pipeline import synthetic_batch as synthetic
from repro.dist.sharding import make_mesh
from repro.ckpt.disk import CheckpointManager
from repro.ft.failures import FailureInjector, FailurePlan
from repro.ft.runtime import (ElasticRuntime, FTPolicy, FTRuntime,
                              stack_view, unstack_view)
from repro.launch import compile_cache
from repro.train.optimizer import AdamWConfig
from repro.train.step import StepOptions, build_train_step, init_state, make_inputs


def run(arch: str, *, smoke: bool = True, steps: int = 100, batch: int = 16,
        seq: int = 128, microbatches: int = 1, abft_mode: str = "off",
        inject_failures: int = 0, ckpt_dir: str = None, resume: bool = False,
        log_every: int = 10, lr: float = 3e-4, seed: int = 0,
        diskless_every: int = 10, mesh=None, total_steps: int = None,
        experts_held: int = 0, expert_offset: int = 0):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    if experts_held:    # one chip's share of an expert-parallel layer
        cfg = cfg.scaled(experts_held=experts_held,
                         expert_offset=expert_offset)
    shape = ShapeConfig("cli", seq, batch, "train")
    if mesh is None:
        mesh = make_mesh((1, 1), ("data", "model"))
    opts = StepOptions(microbatches=microbatches, abft_mode=abft_mode,
                       remat=False if smoke else True)
    total = total_steps or steps  # schedule horizon (resume consistency)
    adamw = AdamWConfig(lr=lr, total_steps=total,
                        warmup_steps=max(total // 20, 1))

    with jax.set_mesh(mesh):
        step_fn, in_sh, out_sh = build_train_step(cfg, mesh, shape, adamw, opts)
        jit_step = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=(0,))
        state = init_state(jax.random.PRNGKey(seed), cfg, opts)
        state = jax.device_put(state, in_sh[0])  # place onto mesh shardings

        data_cfg = DataConfig(cfg.vocab_size, seq, batch, seed=seed)
        start_step = 0
        manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
        if resume and manager and manager.latest_step() is not None:
            latest = manager.latest_step()
            state = manager.restore(latest, jax.eval_shape(lambda: state))
            start_step = int(manager.aux(latest).get("data_step", latest))
            print(f"[train] resumed from step {latest}")
        pipe = DataPipeline(data_cfg, start_step=start_step)

        # FT runtime over a p-way logical shard view of the state (the DP
        # stacking on a single host is simulated with p=4 logical shards)
        p_logical = 4
        ft = FTRuntime(p_logical, FTPolicy(diskless_every=diskless_every,
                                           disk_every=max(steps // 4, 25)),
                       injector=FailureInjector(FailurePlan.random(
                           inject_failures, steps, p_logical, seed))
                       if inject_failures else None,
                       ckpt_manager=manager)

        losses = []
        t0 = time.time()
        i = start_step
        done_steps = 0
        while i < steps:
            # diskless/disk checkpoint cadence (views are p-stacked splits).
            # A view is a copy of the state: build one per use and never
            # hold it across the step — at full width on one chip, state +
            # view + diskless snapshot + step temporaries do not fit.
            ft.maybe_checkpoint(i, _stack_view(state, p_logical),
                                aux={"data_step": i})

            failed = ft.injector.check(i) if ft.injector else None
            if failed is not None:
                stacked = FailureInjector.damage(
                    _stack_view(state, p_logical), failed, p_logical)
                state = _unstack_view(ft.recover(stacked, [failed]), state)
                del stacked
                rollback = ft.diskless.step if ft.diskless.step is not None else i
                print(f"[train] step {i}: shard {failed} lost; diskless "
                      f"recovery -> rollback to step {rollback}")
                i = rollback  # deterministic data pipeline replays exactly

            batch_dev = {k: jnp.asarray(v)
                         for k, v in synthetic(data_cfg, i).items()}
            state, metrics = jit_step(state, batch_dev)
            losses.append(float(metrics["loss"]))
            publish_moe_counters(metrics, cfg)
            if i % log_every == 0:
                print(f"[train] step {i:5d} loss={losses[-1]:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"({(time.time()-t0)/max(done_steps+1,1):.2f}s/step)")
            i += 1
            done_steps += 1
        pipe.close()
        if manager:
            manager.save(steps, state, aux={"data_step": steps}, blocking=True)
        print(f"[train] done: loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
              f"recoveries={ft.recoveries}")
        return losses


def publish_moe_counters(metrics, cfg) -> None:
    """The held-expert layer's step counters on the `repro.obs` bus:
    assignments to this chip's experts, rows the grouped kernel computed
    (tile padding included) and expert-product verify trips."""
    if "moe_assignments" not in metrics:
        return
    held = f"{cfg.expert_offset}-{cfg.expert_offset + cfg.experts_held - 1}"
    obs.counter("repro_moe_assignments_total",
                "token-expert assignments routed to held experts").inc(
        float(metrics["moe_assignments"]), held=held)
    obs.counter("repro_moe_rows_computed_total",
                "rows the grouped expert kernel computed, with padding").inc(
        float(metrics["moe_rows_computed"]))
    obs.counter("repro_abft_trips_total",
                "ABFT residual trips by protected surface").inc(
        float(metrics["moe_trips"]), surface="moe.expert")


# stacked DP views moved to ft.runtime (shared with ElasticRuntime); kept
# as module aliases for callers of the original driver API
_stack_view = stack_view
_unstack_view = unstack_view


# ---------------------------------------------------------------------------
# pod-loss drill: shrink -> resume -> re-grow, with a parity reference
# ---------------------------------------------------------------------------


def run_elastic_drill(arch: str = "qwen2-0.5b", *, steps: int = 10,
                      kill_pod_at: int = 4, regrow_at: int = None,
                      batch: int = 8, seq: int = 32,
                      mesh_shape=(2, 2, 2), lr: float = 1e-3, seed: int = 0,
                      ckpt_dir: str = None, diskless_every: int = 1,
                      disk_every: int = 1, verbose: bool = True) -> dict:
    """Drive `ElasticRuntime` through the ROADMAP's pod-loss drill.

    Timeline: train on the full ``(pod, data, model)`` mesh; at step
    `kill_pod_at` a pod dies -> rung 3 shrinks onto the survivor mesh
    (rollback to the latest checkpoint, reshard params + ZeRO-1 opt state,
    re-split the batch, recompile) and replays forward; at `regrow_at`
    the pod returns -> re-grow onto the full mesh, no rollback.

    Afterwards a REFERENCE run builds the survivor mesh from scratch,
    restores the same disk checkpoint at the rollback step, and replays
    the post-shrink window — the drilled run must match it step-for-step
    (bit-identical restored params, equal losses).  Returns a
    JSON-serializable report: losses of both runs, the parity result, and
    the elastic transition costs (reshard wall, bytes moved, recompile
    time) for BENCH_PR4.json.
    """
    n_needed = math.prod(mesh_shape)
    if len(jax.devices()) < n_needed:
        raise RuntimeError(
            f"drill mesh {mesh_shape} needs {n_needed} devices, have "
            f"{len(jax.devices())} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_needed} before "
            "importing jax")
    assert kill_pod_at >= 1, "need at least one checkpointed step pre-kill"
    cfg = smoke_config(arch)
    shape = ShapeConfig("drill", seq, batch, "train")
    adamw = AdamWConfig(lr=lr, total_steps=steps,
                        warmup_steps=max(steps // 10, 1))
    opts = StepOptions(remat=False)
    policy = FTPolicy(diskless_every=diskless_every, disk_every=disk_every)
    tmp = None
    if ckpt_dir is None:
        tmp = tempfile.TemporaryDirectory()
        ckpt_dir = tmp.name

    mesh = make_mesh(mesh_shape, ("pod", "data", "model"))
    # keep every step: the parity reference re-restores the rollback ckpt
    rt = ElasticRuntime(cfg, shape, mesh, adamw=adamw, opts=opts,
                        policy=policy,
                        ckpt_manager=CheckpointManager(ckpt_dir,
                                                       keep=steps + 1))
    state = rt.init_state(seed)
    losses = {}
    killed = regrown = False
    rollback = None
    shrink_rep = regrow_rep = None
    post_shrink_host = None
    t_start = time.time()
    i = 0
    while i < steps:
        if not killed and i == kill_pod_at:
            rt.ckpt.wait()        # the async save for step i-1 must land
            state, rollback, shrink_rep = rt.lose_pod(state)
            killed = True
            # preserve the PRE-KILL rollback checkpoint: the replay below
            # re-saves the same steps (overwriting them with post-restore
            # state), and the parity reference must restore bits the
            # drilled run cannot have rewritten — otherwise a restore bug
            # would be persisted and mirrored, and the rung-3a solve error
            # would compare the restored state with itself
            src = Path(ckpt_dir) / f"step_{rollback}"
            if not src.exists():
                raise RuntimeError(
                    f"no disk checkpoint at rollback step {rollback} for "
                    "the parity reference (set disk_every=1 for drills)")
            ref_dir = Path(ckpt_dir) / "ref"
            shutil.copytree(src, ref_dir / f"step_{rollback}",
                            dirs_exist_ok=True)
            if verbose:
                print(f"[drill] step {i}: pod lost -> "
                      f"{shrink_rep.mesh_to} via {shrink_rep.restore_path}, "
                      f"rollback to {rollback}, "
                      f"reshard {shrink_rep.reshard_wall_s*1e3:.0f}ms, "
                      f"compile {shrink_rep.compile_s:.1f}s")
            post_shrink_host = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), state)
            i = rollback          # deterministic pipeline replays exactly
            continue
        if killed and not regrown and regrow_at is not None \
                and i == regrow_at:
            state, regrow_rep = rt.regrow(state, at_step=i)
            regrown = True
            if verbose:
                print(f"[drill] step {i}: pod returned -> "
                      f"{regrow_rep.mesh_to} "
                      f"(reshard {regrow_rep.reshard_wall_s*1e3:.0f}ms, "
                      f"executable "
                      f"{'reused' if regrow_rep.reused_executable else 'recompiled'})")
        rt.checkpoint(i, state)
        state, m = rt.train_step(i, state)
        losses[i] = float(m["loss"])
        if verbose and i % max(steps // 10, 1) == 0:
            print(f"[drill] step {i:4d} loss={losses[i]:.4f} "
                  f"mesh={dict(rt.gen.mesh.shape)}")
        i += 1
    drill_wall = time.time() - t_start
    rt.ckpt.wait()
    rt.close()

    # ---- reference: survivor mesh FROM SCRATCH, restored at the same step
    parity_end = regrow_at if regrown else steps
    ref_losses = {}
    params_bitwise_equal = None
    params_max_abs_diff = None
    if killed:
        from repro.ckpt.elastic import reshard_restore
        ref_mesh = make_mesh(shrink_rep.mesh_to.values(),
                             shrink_rep.mesh_to.keys())
        ref_rt = ElasticRuntime(cfg, shape, ref_mesh, adamw=adamw,
                                opts=opts, policy=policy)
        manager = CheckpointManager(str(Path(ckpt_dir) / "ref"))
        ref_state = reshard_restore(manager, rollback,
                                    ref_rt.gen.state_shapes, ref_mesh,
                                    opts, cfg)
        if post_shrink_host is not None:
            ref_host = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), ref_state)
            pairs = list(zip(jax.tree.leaves(post_shrink_host),
                             jax.tree.leaves(ref_host)))
            params_bitwise_equal = all(
                np.array_equal(a, b, equal_nan=True) for a, b in pairs)
            # rung 3a restores via the checksum SOLVE (float arithmetic):
            # near-exact, not bit-exact — quantify instead of just flagging
            params_max_abs_diff = float(max(
                np.max(np.abs(a.astype(np.float64) - b.astype(np.float64)))
                if a.size else 0.0 for a, b in pairs))
        for j in range(rollback, parity_end):
            ref_state, m = ref_rt.train_step(j, ref_state)
            ref_losses[j] = float(m["loss"])
        ref_rt.close()

    window = [k for k in sorted(ref_losses) if k in losses]
    diffs = [abs(losses[k] - ref_losses[k]) for k in window]
    max_diff = max(diffs) if diffs else None
    report = {
        "arch": arch, "mesh": list(mesh_shape),
        "survivor_mesh": shrink_rep.mesh_to if shrink_rep else None,
        "steps": steps, "kill_pod_at": kill_pod_at, "regrow_at": regrow_at,
        "rollback_step": rollback,
        "losses": {str(k): v for k, v in sorted(losses.items())},
        "ref_losses": {str(k): v for k, v in sorted(ref_losses.items())},
        "parity": {
            "window": [rollback, parity_end] if killed else None,
            "steps_compared": len(window),
            "max_abs_loss_diff": max_diff,
            "loss_parity": (max_diff is not None and max_diff == 0.0),
            "params_bitwise_equal": params_bitwise_equal,
            "params_max_abs_diff": params_max_abs_diff,
        },
        "shrink": shrink_rep.summary() if shrink_rep else None,
        "regrow": regrow_rep.summary() if regrow_rep else None,
        "recoveries": rt.recoveries,
        "drill_wall_s": drill_wall,
    }
    if verbose:
        p = report["parity"]
        print(f"[drill] parity over steps {p['window']}: "
              f"{p['steps_compared']} compared, "
              f"max |dloss|={p['max_abs_loss_diff']}, "
              f"params bit-identical={p['params_bitwise_equal']}")
    if tmp is not None:
        tmp.cleanup()
    return report


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--abft", default="off")
    ap.add_argument("--inject-failures", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--experts-held", type=int, default=0,
                    help="MoE: experts this chip holds (0: all)")
    ap.add_argument("--expert-offset", type=int, default=0,
                    help="MoE: the first held expert")
    # elastic drill flags (ft.runtime.ElasticRuntime end-to-end)
    ap.add_argument("--kill-pod-at-step", type=int, default=None,
                    help="run the pod-loss drill: lose a pod at this step")
    ap.add_argument("--regrow-at-step", type=int, default=None,
                    help="re-grow onto the full mesh at this step")
    ap.add_argument("--drill-mesh", default="2x2x2",
                    help="drill mesh PxDxM (needs P*D*M host devices)")
    ap.add_argument("--drill-json", default=None,
                    help="write the drill report JSON here")
    args = ap.parse_args()
    if args.kill_pod_at_step is not None:
        mesh_shape = tuple(int(x) for x in args.drill_mesh.split("x"))
        report = run_elastic_drill(
            args.arch, steps=args.steps, kill_pod_at=args.kill_pod_at_step,
            regrow_at=args.regrow_at_step, batch=args.batch, seq=args.seq,
            mesh_shape=mesh_shape, lr=args.lr, ckpt_dir=args.ckpt_dir)
        if args.drill_json:
            with open(args.drill_json, "w") as fh:
                json.dump(report, fh, indent=1)
            print(f"[drill] report -> {args.drill_json}")
        return
    run(args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
        seq=args.seq, microbatches=args.microbatches, abft_mode=args.abft,
        inject_failures=args.inject_failures, ckpt_dir=args.ckpt_dir,
        resume=args.resume, lr=args.lr, experts_held=args.experts_held,
        expert_offset=args.expert_offset)


if __name__ == "__main__":
    main()
