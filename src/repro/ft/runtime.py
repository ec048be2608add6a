"""Fault-tolerant training runtimes: the paper's recovery timeline (§3.3) as
a training-loop wrapper, grown into an elastic runtime that survives
*topology* loss, not just shard loss.

The recovery LADDER, cheapest rung first (each rung handles what the one
below cannot):

  1. **in-step ABFT** — silent corruption inside a step is detected,
     located and corrected by the checksums fused into the matmuls
     (`core.abft_gemm`, `kernels.abft_matmul`) and riding the gradient
     collective (`dist.collectives.abft_psum`); zero rollback, the step
     simply completes with the repaired values (compiled into every
     generation via `StepOptions.abft_mode` / `abft_reduce`).
  2. **diskless rollback** — a lost DP shard on an unchanged topology is
     rebuilt from the rotated checksum shards (`ckpt.diskless`); bounded
     rollback to the last encode, no disk.
  3. **elastic reshard** — the hardware is actually gone (pod loss): build
     a survivor mesh, re-place params AND ZeRO-1 opt state through the
     mesh-agnostic `train.step.state_specs`, re-split the global batch
     (`data.pipeline.resplit` — sample order unchanged), recompile, and
     resume; the mirror operation re-grows when the pod returns.  Rung 3a
     reuses the surviving diskless state when the loss fits its capacity
     (`DisklessCheckpoint.reshard`), rung 3b restores from disk
     (`ckpt.elastic.reshard_restore`).

`FTRuntime` wraps rungs 1-2 around a caller-built step function (the
original runtime, kept as-is for single-topology loops).  `ElasticRuntime`
OWNS the step: it builds and versions a `MeshGeneration` — mesh +
shardings + compiled step + data split + diskless/disk cadence as one
bundle — and switches generations on `lose_pod()` / `regrow()`, logging an
`ElasticReport` (placement diff, bytes moved, reshard wall, recompile
time) per switch.

Straggler mitigation: synchronous SPMD has no per-step laggards to chase —
the mitigation is (a) the diskless encode cadence bounds recovery work,
(b) `slow_pod_threshold` demotes a persistently slow pod via the elastic
path (the 1000-node answer: drop it, keep the batch), and (c) data loading
is prefetched off the critical path (data.pipeline).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import weakref
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs import profiler as obs_profiler
from repro.chaos.faults import register_surface
from repro.ckpt.diskless import DisklessCheckpoint
from repro.ft.failures import FailureInjector, SDCInjector

__all__ = ["FTPolicy", "FTRuntime", "ElasticRuntime", "MeshGeneration",
           "ElasticReport", "ScrubReport", "StragglerDetector", "stack_view",
           "unstack_view"]

# the protection domain this module owns (repro.chaos campaigns drill it):
# TOPOLOGY faults — a pod that is gone (platform-signaled) or a pod that is
# merely persistently slow (step-time EWMA straggler detector) — handled by
# the rung-3 elastic shrink/re-grow ladder.
register_surface(
    "ft.runtime/topology", owner=__name__, protected=True,
    promise="tolerance",
    detector="pod loss: platform failure signal; slow pod: per-pod "
             "step-time EWMA exceeding slow_pod_threshold x the median "
             "(StragglerDetector) — both demote through lose_pod()",
    kinds=("pod_loss", "slow_pod"),
    note="rung 3b (disk restore) resumes bit-identically (PR 4 drill); "
         "rung 3a (diskless checksum solve) is near-exact, hence the "
         "tolerance promise; demotion rolls back to the last checkpoint "
         "and replays deterministically")
# at-rest scrub: upgrades the faults.py placeholders to protected.  The
# cadenced `ElasticRuntime.scrub` re-runs the diskless encode over the live
# stacked state and compares against the checksums held since the encode
# point — a silent DRAM flip in resident params or opt moments trips the
# residual and rolls back to the snapshot (rung "scrub:diskless").
register_surface(
    "state.params_at_rest", owner=__name__, protected=True,
    promise="tolerance",
    detector="checksum-on-write / verify-on-read: the scrub cadence "
             "recomputes the diskless encode of the live state and "
             "compares leafwise against the held checksums "
             "(DisklessCheckpoint.verify); a trip restores the snapshot",
    kinds=("dram_params",),
    note="valid only at encode-point steps (state unchanged since encode); "
         "the serve-side params scrub lives in serve.engine")
register_surface(
    "state.opt_state_at_rest", owner=__name__, protected=True,
    promise="tolerance",
    detector="same scrub as params: the diskless encode covers the FULL "
             "stacked state, AdamW moments included, so an at-rest flip "
             "in the opt state trips the same leafwise residual",
    kinds=("dram_opt_state",),
    note="rollback restores the whole snapshot (params + opt + step)")


@dataclasses.dataclass(frozen=True)
class FTPolicy:
    """Recovery-budget knobs for `FTRuntime`.  `diskless_every` sets the
    checksum-encode cadence (recovery replays zero steps but costs one
    encode per cadence); `disk_every` the async disk-snapshot cadence (the
    fallback when more than `f` shards die at once); `f` the simultaneous
    failures the diskless encoding survives (paper's checksum capacity);
    `slow_pod_threshold` demotes a pod persistently slower than this
    multiple of the median step time via the elastic path (EWMA-smoothed:
    `straggler_alpha` is the smoothing factor, `straggler_warmup` the
    per-pod observations required before the detector may trip)."""
    diskless_every: int = 10       # encode cadence (steps)
    disk_every: int = 100          # async disk snapshot cadence
    f: int = 1                     # simultaneous failures survivable
    slow_pod_threshold: float = 3.0  # x median step-time EWMA -> demote pod
    straggler_alpha: float = 0.5   # EWMA smoothing of per-pod step times
    straggler_warmup: int = 3      # observations before the detector trips
    # at-rest scrub cadence (steps); 0 = off.  A scrub only fires at steps
    # that are also encode points (the verify needs unchanged state), so a
    # useful cadence is a multiple of diskless_every — the drills run both
    # at 1.  Off the critical path: the verify reads state the step is not
    # mutating and can overlap the next step's compute.
    scrub_every: int = 0


def stack_view(state, p: int):
    """View each float leaf as [p, ...] by splitting its leading dim when
    divisible (single-host stand-in for the DP stacking the diskless
    protocol checksums over)."""
    def stack(x):
        if x.ndim >= 1 and x.shape[0] % p == 0 and jnp.issubdtype(
                x.dtype, jnp.floating):
            return x.reshape((p, x.shape[0] // p) + x.shape[1:])
        return x
    return jax.tree.map(stack, state)


def unstack_view(stacked, like):
    """Inverse of `stack_view` against the reference shapes in `like`."""
    def unstack(x, ref):
        if x.shape != ref.shape:
            return x.reshape(ref.shape)
        return x
    return jax.tree.map(unstack, stacked, like)


def _pub_rung(rung: str, wall_s: float, step: Optional[int] = None,
              compile_s: Optional[float] = None,
              warm_s: Optional[float] = None, **attrs) -> None:
    """Publish one recovery-ladder firing to the obs bus: the
    ``repro_recoveries_total{rung=...}`` counter plus a ``recovery/<rung>``
    span carrying the measured wall (and the compile/warm split when the
    caller has it — `MeshGeneration` measures compile separately, so the
    elastic rungs always do)."""
    obs.counter("repro_recoveries_total",
                "recovery-ladder rungs fired").inc(rung=rung)
    obs.recovery(rung, wall_s, step=step, compile_s=compile_s,
                 warm_s=warm_s, **attrs)


class FTRuntime:
    """Wraps a step function with detection/recovery (single-host substrate:
    the DP axis is the stacked leading dim of the replicated state views)."""

    def __init__(self, p: int, policy: FTPolicy,
                 injector: Optional[FailureInjector] = None,
                 ckpt_manager=None,
                 sdc_injector: Optional[SDCInjector] = None):
        self.p = p
        self.policy = policy
        # `injector` accepts one FailureInjector or a SEQUENCE of them —
        # multi-fault episodes thread several concurrent erasure sources
        # through one runtime; every injector is drained each step and
        # same-step failures recover JOINTLY (one solve over all lost
        # shards, bounded by the checksum capacity f).
        if injector is None:
            self.injectors: Tuple[FailureInjector, ...] = ()
        elif isinstance(injector, FailureInjector):
            self.injectors = (injector,)
        else:
            self.injectors = tuple(injector)
        self.sdc_injector = sdc_injector
        self.ckpt = ckpt_manager
        self.diskless = DisklessCheckpoint(p, policy.f)
        self.recoveries = {"diskless": 0, "disk": 0, "sdc": 0}
        self.step_times = []

    @property
    def injector(self) -> Optional[FailureInjector]:
        """Back-compat single-injector view (first of `injectors`)."""
        return self.injectors[0] if self.injectors else None

    def _failed_shards(self, step: int) -> List[int]:
        """Drain EVERY injector's events for `step` (an injector may plan
        several same-step losses): the deduped joint failure set."""
        failed: List[int] = []
        for inj in self.injectors:
            while True:
                shard = inj.check(step)
                if shard is None:
                    break
                if shard not in failed:
                    failed.append(shard)
        return failed

    def maybe_checkpoint(self, step: int, state, aux=None):
        if step % self.policy.diskless_every == 0:
            self.diskless.encode(state, step)
        if self.ckpt is not None and step % self.policy.disk_every == 0:
            self.ckpt.save(step, state, aux=aux)

    def step(self, step_idx: int, state, run_step: Callable,
             run_step_sdc: Optional[Callable] = None):
        """Run one training step with failure check + recovery.

        `run_step_sdc(state, events)` runs a step variant with an SDC
        injection + `abft_reduce` protection (train.step.StepOptions):
        when the SDC plan fires at this step the corrupted variant runs and
        the ABFT checksum riding the gradient psum repairs the reduction
        in-flight (counted under recoveries["sdc"]).  `events` is the
        fired ``(shard, delta)`` payload — or a TUPLE of payloads when the
        plan schedules several faults for one step (each lands in a
        different protected reduction; see `SDCPlan`/`abft_psum_tree`) —
        passed through so the drill can select/parameterize the injected
        step (injection location is compile-time static in StepOptions, so
        a drill pre-builds one step per planned event set).
        """
        t0 = time.time()
        failed = self._failed_shards(step_idx)
        if failed:
            for shard in failed:
                state = FailureInjector.damage(state, shard, self.p)
            state = self.recover(state, failed)
        # only consume SDC events when there is a handler to drive them —
        # otherwise the events stay planned instead of silently vanishing
        sdc = (self.sdc_injector.check_all(step_idx)
               if self.sdc_injector is not None and run_step_sdc is not None
               else ())
        if sdc:
            # counts SDC drills DRIVEN (injection reached the reduction);
            # whether it was merely detected or also repaired is the step's
            # abft_reduce mode, visible in metrics["abft_ok"]
            self.recoveries["sdc"] += 1
            obs.event("fault/inject", step=step_idx,
                      surface="train.step/grad_reduce", kind="sdc_reduce",
                      n=len(sdc))
            out = run_step_sdc(state, sdc[0] if len(sdc) == 1 else sdc)
        else:
            out = run_step(state)
        self.step_times.append(time.time() - t0)
        return out

    def recover(self, damaged_state, failed):
        """Diskless first (paper's path), disk as fallback."""
        if self.diskless.step is not None and len(failed) <= self.policy.f:
            self.recoveries["diskless"] += 1
            t0 = time.time()
            out = self.diskless.recover(damaged_state, failed)
            _pub_rung("diskless", time.time() - t0, shards=len(failed))
            return out
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            self.recoveries["disk"] += 1
            latest = self.ckpt.latest_step()
            t0 = time.time()
            out = self.ckpt.restore(latest, damaged_state)
            _pub_rung("disk", time.time() - t0, rollback_step=latest)
            return out
        raise RuntimeError(
            f"unrecoverable: {len(failed)} failures, capacity f="
            f"{self.policy.f}, no disk checkpoint")


# ---------------------------------------------------------------------------
# straggler detection: per-pod step-time EWMA
# ---------------------------------------------------------------------------


class StragglerDetector:
    """Per-pod step-time EWMA; trips when one pod's EWMA exceeds
    ``threshold`` x the median EWMA of the OTHER pods.

    Synchronous SPMD means the global step runs at the slowest pod's pace,
    so per-pod walls come from a heartbeat (each pod's host callback
    reports its own step wall; `ElasticRuntime.train_step` synthesizes a
    uniform heartbeat when none is installed).  The EWMA smooths one-off
    hiccups away — only a *persistently* slow pod trips, and only after
    `warmup` observations — and the median baseline keeps a uniformly
    slow fleet (everyone sharing a slow step) from self-demoting.
    """

    def __init__(self, n_pods: int, threshold: float, *,
                 alpha: float = 0.5, warmup: int = 3):
        self.n_pods = n_pods
        self.threshold = threshold
        self.alpha = alpha
        self.warmup = warmup
        self.ewma = [None] * n_pods
        self.observations = 0

    def observe(self, walls) -> Optional[int]:
        """Feed one step's per-pod walls; returns the pod to demote (the
        worst offender) or None.  Never trips with fewer than 2 pods."""
        if len(walls) != self.n_pods:
            raise ValueError(f"expected {self.n_pods} pod walls, got "
                             f"{len(walls)}")
        a = self.alpha
        self.ewma = [w if e is None else a * w + (1 - a) * e
                     for e, w in zip(self.ewma, walls)]
        self.observations += 1
        if self.n_pods < 2 or self.observations < self.warmup:
            return None
        worst = max(range(self.n_pods), key=lambda i: self.ewma[i])
        others = sorted(e for i, e in enumerate(self.ewma) if i != worst)
        median = others[len(others) // 2]
        if median > 0 and self.ewma[worst] > self.threshold * median:
            return worst
        return None


# ---------------------------------------------------------------------------
# elastic runtime: versioned mesh generations + the full ladder
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MeshGeneration:
    """One versioned bundle: everything a topology needs to take a step.

    Rebuilt (or fetched from the executable cache) on every elastic
    transition; nothing outside the bundle depends on the mesh shape, so
    switching generations IS the topology change."""
    gen: int                    # monotonically increasing generation id
    mesh: jax.sharding.Mesh
    step_fn: Callable           # AOT-compiled (state, batch) -> (state, metrics)
    in_shardings: Tuple         # (state shardings, batch shardings)
    out_shardings: Tuple
    state_shapes: dict          # eval_shape of the state tree (mesh-agnostic)
    dp_extent: int              # product of the non-"model" axis sizes
    split: int                  # data-pipeline split (batch-dividing DP extent)
    build_s: float              # python build (specs, tracers) wall
    compile_s: float            # lower+compile wall (0.0 when cache-reused)
    reused: bool = False        # executable came from the generation cache


@dataclasses.dataclass(frozen=True)
class ScrubReport:
    """One at-rest scrub that TRIPPED (clean scrubs return None)."""
    step: int                   # encode-point step the scrub verified
    leaf: str                   # first leaf whose checksum residual tripped
    residual: float             # worst relative residual observed
    wall_s: float               # verify + restore wall
    rolled_back: bool           # snapshot restore applied


@dataclasses.dataclass(frozen=True)
class ElasticReport:
    """What one elastic transition did and what it cost — the placement
    diff summary (`ckpt.elastic.plan_reshard`) plus measured walls."""
    kind: str                   # "shrink" | "regrow"
    gen_from: int
    gen_to: int
    mesh_from: dict
    mesh_to: dict
    restore_path: str           # "diskless" (rung 3a) | "disk" (3b) | "live"
    rollback_step: Optional[int]
    n_leaves: int
    n_respecced: int
    bytes_total: int
    bytes_respecced: int
    reshard_wall_s: float
    build_s: float
    compile_s: float
    reused_executable: bool

    def summary(self) -> dict:
        return dataclasses.asdict(self)


class ElasticRuntime(FTRuntime):
    """Owns mesh generations and executes the three-rung recovery ladder.

    Unlike `FTRuntime` (which wraps a caller-built step), this runtime
    BUILDS the step per topology: construction compiles generation 0 on
    `mesh`; `lose_pod()` shrinks onto the survivor mesh (rung 3) and
    `regrow()` returns to the full mesh when the pod comes back.  Rungs
    1-2 ride along unchanged — rung 1 is compiled into every generation
    via `opts`, rung 2 is `maybe_shard_failure` (diskless-first).

    Determinism contract (what the parity drills assert): the data stream
    is global and (seed, step)-deterministic, checkpoints hold global
    arrays, and shardings are mesh-agnostic functions of the state — so a
    drilled shrink resumes bit-identically to a survivor-mesh-from-scratch
    restore of the same checkpoint.
    """

    def __init__(self, cfg, shape, mesh, *, adamw=None, opts=None,
                 policy: Optional[FTPolicy] = None, data_cfg=None,
                 ckpt_manager=None, injector=None, sdc_injector=None):
        from repro.data.pipeline import DataConfig, DataPipeline
        from repro.train.optimizer import AdamWConfig
        from repro.train.step import StepOptions

        obs_profiler.install()
        self.cfg = cfg
        self.shape = shape
        self.adamw = adamw or AdamWConfig()
        self.opts = opts or StepOptions()
        self.full_mesh = mesh
        self._next_gen = 0
        self._gen_cache = {}       # mesh-shape key -> MeshGeneration
        self.reports = []
        gen = self._build_generation(mesh)
        super().__init__(gen.dp_extent, policy or FTPolicy(),
                         injector=injector, ckpt_manager=ckpt_manager,
                         sdc_injector=sdc_injector)
        self.gen = gen
        self.recoveries["elastic"] = 0
        self.recoveries["demote"] = 0
        self.data_cfg = data_cfg or DataConfig(
            cfg.vocab_size, shape.seq_len, shape.global_batch)
        self.pipe = DataPipeline(self.data_cfg, split=gen.split)
        # straggler path: `pod_heartbeat(step, wall) -> per-pod walls` is
        # each pod's host callback reporting its own step time (drills
        # inject a delay into one pod's callback — chaos FaultSpec
        # kind="slow_pod"); None = synthesize a uniform heartbeat
        self.pod_heartbeat = None
        self._straggler = self._fresh_straggler(gen.mesh)
        self._obs_id = id(self)
        self._attach_straggler()

    def _attach_straggler(self):
        """Attach the straggler detector through the bus: `train_step`
        publishes each step's per-pod walls as a ``train/pod_walls`` event
        and the detector consumes them via ``obs.subscribe`` — the
        callback seam the ROADMAP trainer-shell item asks for.  The
        subscription holds only a weakref to the runtime; a dropped
        runtime detaches itself on its next event."""
        wr = weakref.ref(self)

        def _feed(ev, _wr=wr):
            rt = _wr()
            if rt is None:
                obs.unsubscribe(_feed)
                return
            if (ev.name != "train/pod_walls"
                    or ev.attrs.get("runtime") != rt._obs_id):
                return
            slow = rt._straggler.observe(list(ev.attrs["walls"]))
            rt._slow_pod = slow
            if slow is not None:
                obs.counter("repro_straggler_trips_total",
                            "EWMA straggler detector trips").inc()
                obs.event("straggler/trip", step=ev.step, pod=slow,
                          ewma=list(rt._straggler.ewma))

        self._obs_sub = _feed
        obs.subscribe(_feed)

    # -- generation lifecycle ------------------------------------------------

    def _build_generation(self, mesh) -> MeshGeneration:
        """Build (or cache-fetch) the full bundle for `mesh`.

        The executable cache is keyed on the mesh SHAPE: re-growing onto a
        previously seen topology reuses its compiled step (the production
        move — the old executable was never discarded), so only
        first-contact topologies pay the recompile."""
        from repro.dist import sharding as shd
        from repro.train.step import (build_train_step, init_state,
                                      make_inputs)

        key = tuple(mesh.shape.items())
        cached = self._gen_cache.get(key)
        if cached is not None:
            gen = dataclasses.replace(
                cached, gen=self._next_gen, compile_s=0.0, reused=True)
            self._next_gen += 1
            return gen

        t0 = time.time()
        with jax.set_mesh(mesh):
            fn, in_sh, out_sh = build_train_step(
                self.cfg, mesh, self.shape, self.adamw, self.opts)
            state_shapes = jax.eval_shape(
                functools.partial(init_state, cfg=self.cfg, opts=self.opts,
                                  mesh=mesh),
                jax.random.PRNGKey(0))
            build_s = time.time() - t0
            t1 = time.time()
            compiled = jax.jit(
                fn, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=(0,)).lower(
                    state_shapes, make_inputs(self.cfg, self.shape)).compile()
            compile_s = time.time() - t1

        bspec = shd.batch_specs(mesh, self.shape.global_batch)[0]
        split = shd._entry_extent(mesh, bspec)
        dp_extent = 1
        for a in shd.dp_axes(mesh):
            dp_extent *= mesh.shape[a]
        gen = MeshGeneration(
            gen=self._next_gen, mesh=mesh, step_fn=compiled,
            in_shardings=in_sh, out_shardings=out_sh,
            state_shapes=state_shapes, dp_extent=dp_extent, split=split,
            build_s=build_s, compile_s=compile_s)
        self._next_gen += 1
        self._gen_cache[key] = gen
        return gen

    def init_state(self, seed: int = 0):
        """Fresh state placed onto the current generation's shardings."""
        from repro.train.step import init_state
        with jax.set_mesh(self.gen.mesh):
            state = init_state(jax.random.PRNGKey(seed), self.cfg, self.opts,
                               self.gen.mesh)
            return jax.device_put(state, self.gen.in_shardings[0])

    # -- the step + cadence --------------------------------------------------

    def place_batch(self, step: int):
        """The deterministic global batch for `step`, placed for the
        current generation (same stream regardless of topology)."""
        return jax.device_put(
            {k: jnp.asarray(v) for k, v in self.pipe.batch_at(step).items()},
            self.gen.in_shardings[1])

    def _fresh_straggler(self, mesh) -> StragglerDetector:
        return StragglerDetector(
            mesh.shape.get("pod", 1), self.policy.slow_pod_threshold,
            alpha=self.policy.straggler_alpha,
            warmup=self.policy.straggler_warmup)

    def train_step(self, step_idx: int, state):
        """Run step `step_idx` under the current generation.  Feeds the
        per-pod heartbeat into the straggler detector; poll
        `maybe_straggler()` after the step and demote via `demote_pod`."""
        batch = self.place_batch(step_idx)
        obs.set_step(step_idx)
        with obs.span("train/step", step=step_idx, gen=self.gen.gen):
            t0 = time.time()
            state, metrics = self.gen.step_fn(state, batch)
            wall = time.time() - t0
        self.step_times.append(wall)
        obs.counter("repro_train_steps_total", "elastic train steps").inc()
        n_pods = self._straggler.n_pods
        walls = (self.pod_heartbeat(step_idx, wall)
                 if self.pod_heartbeat is not None else [wall] * n_pods)
        # the straggler detector consumes this through obs.subscribe
        # (`_attach_straggler`) — the on_step hook seam, done as the bus
        obs.event("train/pod_walls", step=step_idx, walls=list(walls),
                  runtime=self._obs_id)
        return state, metrics

    def maybe_straggler(self) -> Optional[int]:
        """The pod the EWMA detector wants demoted (None = all healthy)."""
        return getattr(self, "_slow_pod", None)

    def demote_pod(self, state, pod: int):
        """Demote a persistently slow pod through the elastic rung: the
        1000-node answer is to DROP it and keep the batch — `lose_pod()`
        shrinks onto the survivor mesh exactly as if the pod had died
        (rollback to the last checkpoint, reshard, replay), and the
        returned `ElasticReport` carries the cost.  `pod` is the detector's
        index (symbolic on this substrate: the survivor mesh shrinks the
        pod axis; on a real fleet it names the slice to drain).  Returns
        ``(state, rollback_step, report)``."""
        state, rollback, report = self.lose_pod(state)
        self.recoveries["demote"] += 1
        self._slow_pod = None
        _pub_rung("demote:" + report.restore_path, report.reshard_wall_s,
                  compile_s=report.compile_s,
                  warm_s=report.reshard_wall_s, pod=pod)
        return state, rollback, report

    def checkpoint(self, step: int, state):
        """Cadenced rung-2/3 state capture: diskless over the stacked view,
        disk over the GLOBAL state (elastic restore needs global leaves).
        The saved data state carries THIS step as its cursor — the runtime
        fetches batches by step (`pipe.batch_at`), so the pipeline's own
        prefetch cursor is not the resume point."""
        if step % self.policy.diskless_every == 0:
            self.diskless.encode(stack_view(state, self.p), step)
        if self.ckpt is not None and step % self.policy.disk_every == 0:
            self.ckpt.save(step, state, aux={
                "data_step": step,
                "data": dict(self.pipe.state_dict(), step=step),
                "gen": self.gen.gen, "mesh": dict(self.gen.mesh.shape)})

    # -- at-rest scrub (state.params_at_rest / state.opt_state_at_rest) ------

    def scrub(self, step: int, state):
        """Cadenced at-rest integrity scrub.  Returns ``(state, report)``
        with ``report=None`` when the scrub did not fire or found the
        state clean.

        Checksum-on-write / verify-on-read: only fires at steps where the
        diskless encode was taken THIS step (``diskless.step == step``), so
        the live state is supposed to be bit-identical to the encode-point
        state and any checksum residual is a DRAM flip — in params, opt
        moments, or the step counter alike (the encode covers the full
        stacked state).  A trip restores the snapshot (whose integrity the
        same checksums vouch for) through the rung-2 path and counts under
        ``recoveries["scrub"]``."""
        if not self.policy.scrub_every or step % self.policy.scrub_every:
            return state, None
        if self.diskless.step != step:
            return state, None
        t0 = time.time()
        stacked = stack_view(state, self.p)
        ok, leaf, resid = self.diskless.verify(stacked)
        if ok:
            return state, None
        self.recoveries["scrub"] = self.recoveries.get("scrub", 0) + 1
        obs.counter("repro_detections_total",
                    "checksum/invariant trips").inc(
            surface="state.at_rest")
        obs.event("fault/detect", step=step, surface="state.at_rest",
                  detector="diskless_verify", leaf=str(leaf))
        obs.histogram("repro_scrub_residual",
                      "at-rest scrub checksum residuals").observe(
            float(resid))
        restored = unstack_view(self.diskless.recover(stacked, []), state)
        state = jax.device_put(restored, self.gen.in_shardings[0])
        report = ScrubReport(step=step, leaf=leaf, residual=resid,
                             wall_s=time.time() - t0, rolled_back=True)
        _pub_rung("scrub:diskless", report.wall_s, step=step,
                  leaf=str(leaf), residual=float(resid))
        return state, report

    # -- rung 2: same-topology shard loss ------------------------------------

    def maybe_shard_failure(self, step: int, state):
        """Drive the `FailureInjector`(s) through rung 2.  Returns
        ``(state, rollback_step or None)``; on a hit the state is the
        recovered ENCODE-point state and the caller replays from
        `rollback_step` (the deterministic pipeline makes replay exact).
        EVERY injector is drained for this step and concurrent losses
        recover JOINTLY — one checksum solve over the whole failure set
        while it fits the capacity `f`.  Diskless-first; disk fallback
        restores the GLOBAL state this runtime's `checkpoint` saves (not
        the stacked view)."""
        failed = self._failed_shards(step)
        if not failed:
            return state, None
        obs.event("fault/detect", step=step, surface="ft.runtime/shards",
                  detector="failure_signal", shards=len(failed))
        t0 = time.time()
        if self.diskless.step is not None and len(failed) <= self.policy.f:
            stacked = stack_view(state, self.p)
            for shard in failed:
                stacked = FailureInjector.damage(stacked, shard, self.p)
            self.recoveries["diskless"] += 1
            stacked = self.diskless.recover(stacked, failed)
            state = unstack_view(stacked, state)
            rollback = self.diskless.step
            rung = "diskless"
        elif self.ckpt is not None and self.ckpt.latest_step() is not None:
            self.recoveries["disk"] += 1
            rollback = self.ckpt.latest_step()
            state = self.ckpt.restore(rollback, self.gen.state_shapes)
            rung = "disk"
        else:
            raise RuntimeError(
                "shard loss with no diskless encode and no disk checkpoint")
        state = jax.device_put(state, self.gen.in_shardings[0])
        _pub_rung(rung, time.time() - t0, step=step, shards=len(failed),
                  rollback_step=rollback)
        return state, rollback

    # -- rung 3: topology change ---------------------------------------------

    def _switch(self, gen: MeshGeneration, at_step: Optional[int]):
        self.gen = gen
        self.p = gen.dp_extent
        self.pipe = self.pipe.resplit(gen.split, at_step=at_step)
        # pod count changed: stale EWMAs would misattribute; start fresh
        self._straggler = self._fresh_straggler(gen.mesh)
        self._slow_pod = None

    def lose_pod(self, state, failed_pods: int = 1):
        """Rung 3: a pod is gone.  Shrink onto the survivor mesh.

        Returns ``(state_on_survivors, rollback_step, report)``.  Restore
        path: rung 3a when the dead pod's slice of the diskless stacking
        fits the checksum capacity `f` (state survives in memory, zero
        rollback past the encode point); rung 3b otherwise (latest disk
        checkpoint through `ckpt.elastic.reshard_restore`).
        """
        from repro.ckpt.elastic import (plan_reshard, reshard_restore,
                                        reshard_state, survivor_mesh)

        old = self.gen
        new_mesh = survivor_mesh(failed_pods=failed_pods, mesh=old.mesh)
        gen = self._build_generation(new_mesh)
        plan = plan_reshard(old.state_shapes, old.mesh, new_mesh,
                            self.opts, self.cfg)
        lost_shards = self.p * failed_pods // old.mesh.shape["pod"]
        t0 = time.time()
        if self.diskless.step is not None and lost_shards <= self.policy.f:
            # 3a: recover the dead pod's shards from the checksums and
            # re-encode for the survivor extent — no disk in the loop
            rollback = self.diskless.step
            failed = list(range(self.p - lost_shards, self.p))
            self.diskless = self.diskless.reshard(gen.dp_extent,
                                                  failed=failed)
            restored = unstack_view(self.diskless.snapshot(), state)
            state = reshard_state(restored, new_mesh, self.opts, self.cfg)
            path = "diskless"
        else:
            if self.ckpt is not None:
                self.ckpt.wait()          # flush the in-flight async save
            if self.ckpt is None or self.ckpt.latest_step() is None:
                raise RuntimeError(
                    f"pod loss beyond diskless capacity (lost {lost_shards} "
                    f"shards > f={self.policy.f}) and no disk checkpoint")
            rollback = self.ckpt.latest_step()
            state = reshard_restore(self.ckpt, rollback, old.state_shapes,
                                    new_mesh, self.opts, self.cfg)
            self.diskless = DisklessCheckpoint(gen.dp_extent, self.policy.f)
            path = "disk"
        reshard_wall = time.time() - t0
        self._switch(gen, at_step=rollback)
        self.recoveries["elastic"] += 1
        report = ElasticReport(
            kind="shrink", gen_from=old.gen, gen_to=gen.gen,
            mesh_from=dict(old.mesh.shape), mesh_to=dict(gen.mesh.shape),
            restore_path=path, rollback_step=rollback,
            n_leaves=len(plan.leaves), n_respecced=plan.n_respecced,
            bytes_total=plan.bytes_total,
            bytes_respecced=plan.bytes_respecced,
            reshard_wall_s=reshard_wall, build_s=gen.build_s,
            compile_s=gen.compile_s, reused_executable=gen.reused)
        self.reports.append(report)
        _pub_rung("elastic:" + path, reshard_wall, compile_s=gen.compile_s,
                  warm_s=reshard_wall, gen_to=gen.gen,
                  rollback_step=rollback, reused=gen.reused)
        return state, rollback, report

    def regrow(self, state, mesh=None, at_step: Optional[int] = None):
        """The pod returns: spread the LIVE survivor state back over the
        full mesh (or `mesh`).  Nothing was lost, so no rollback — the
        diskless checkpoint is re-keyed across the grow to keep its
        recovery point.  Pass `at_step` (the step about to run) so the
        re-split pipeline's cursor is the resumption point rather than
        its prefetch position.  Returns ``(state_on_full_mesh, report)``."""
        from repro.ckpt.elastic import plan_reshard, reshard_state

        old = self.gen
        new_mesh = mesh if mesh is not None else self.full_mesh
        gen = self._build_generation(new_mesh)
        plan = plan_reshard(old.state_shapes, old.mesh, new_mesh,
                            self.opts, self.cfg)
        t0 = time.time()
        state = reshard_state(state, new_mesh, self.opts, self.cfg)
        reshard_wall = time.time() - t0
        if self.diskless.step is not None:
            self.diskless = self.diskless.reshard(gen.dp_extent)
        else:
            self.diskless = DisklessCheckpoint(gen.dp_extent, self.policy.f)
        self._switch(gen, at_step=at_step)
        self.recoveries["elastic"] += 1
        report = ElasticReport(
            kind="regrow", gen_from=old.gen, gen_to=gen.gen,
            mesh_from=dict(old.mesh.shape), mesh_to=dict(gen.mesh.shape),
            restore_path="live", rollback_step=None,
            n_leaves=len(plan.leaves), n_respecced=plan.n_respecced,
            bytes_total=plan.bytes_total,
            bytes_respecced=plan.bytes_respecced,
            reshard_wall_s=reshard_wall, build_s=gen.build_s,
            compile_s=gen.compile_s, reused_executable=gen.reused)
        self.reports.append(report)
        _pub_rung("elastic:live", reshard_wall, compile_s=gen.compile_s,
                  warm_s=reshard_wall, gen_to=gen.gen, reused=gen.reused)
        return state, report

    def close(self):
        if getattr(self, "_obs_sub", None) is not None:
            obs.unsubscribe(self._obs_sub)
            self._obs_sub = None
        self.pipe.close()
