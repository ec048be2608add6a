"""Fault-tolerant distributed continuous-batching serving engine.

vLLM-style slot scheduler on top of the framework's decode path:
  * fixed decode batch of `slots`; every engine step decodes ONE token for
    all occupied slots (per-slot positions — slots are never in lockstep),
  * a finished slot (max_new_tokens or EOS) retires immediately and a queued
    request is admitted: its prompt is prefilled as a single sequence and
    the resulting KV cache is scattered into the freed slot,
  * the whole engine state (batched caches, per-slot positions) lives in
    fixed-shape device arrays — two compiled programs total (prefill_1,
    decode_B), no recompilation as requests come and go.

Distribution (``mesh=``): both compiled programs shard over a `repro.dist`
mesh — params via `dist.sharding.infer_param_specs` (Megatron-style
column/row rules over the "model" axis), KV caches via
`dist.sharding.cache_specs` (slot batch over the DP axes), tokens/positions
over the batch entry.  The model body runs auto-sharded exactly like
`train.step.build_serve_step`; the *final projection* of the decode program
is restructured into an explicit row-parallel `shard_map` region: each model
shard computes a partial-logits contribution from its feature slice and the
cross-shard reduction runs through `dist.collectives.abft_psum` — the
paper's Huang-Abraham checksums ride the decode path's collective itself.

Fault tolerance while serving:
  * ``abft_mode="verify"`` carries checksum columns through the projections
    of both programs (matmul-level SDC detection, core.abft_gemm),
  * ``abft_reduce="verify"|"correct"`` checksum-protects the decode-path
    cross-shard logits reduction (collective-level SDC detection/repair).
    Coverage boundary when BOTH are on: the final projection's local
    matmul runs unprotected inside the shard_map region (its protection
    shifts to the collective — checksums are taken of the computed
    partial, so a fault in that one local accumulator is outside both
    envelopes); every other projection keeps matmul-level protection,
  * ``sdc=SDCInjector(...)`` (ft.failures) drills the protected reduction:
    at planned engine steps a bit-flip-sized delta corrupts one model
    shard's contribution AFTER its checksums are taken — mid-collective,
    exactly the paper's transient-fault model — and the engine detects,
    locates, corrects in-flight and records the event in `EngineStats`
    (detections, corrections, recovery latency, per-request TTFT / tok/s).

The verified-unembed shard_map region is partial-manual over {"model"} and
contains only a matmul + one psum; the layer scans stay in the auto-sharded
body (see `repro.dist` on partial-manual regions).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.obs import profiler as obs_profiler
from repro.chaos.faults import SDCInjector, register_surface, scatter_delta
from repro.configs.base import ModelConfig
from repro.dist import sharding as shd
from repro.dist.collectives import abft_psum
from repro.models import transformer as tf
from repro.models.layers import softcap_fn
from repro.train.step import StepOptions

__all__ = ["Request", "ServeEngine", "PagedServeEngine", "EngineStats",
           "SDCEvent", "ScrubEvent"]

# the protection domains/surfaces this module owns (repro.chaos drills
# them): the verified unembed reduction is protected; the KV cache sitting
# in device memory between decode steps is an honest ledger entry
register_surface(
    "serve.engine/logits_reduce", owner=__name__, protected=True,
    promise="bit_identity",
    detector="abft_psum checksums riding the row-parallel unembed's "
             "cross-shard reduction (detect/locate/correct in-flight, "
             "EngineStats records the event)",
    kinds=("sdc_collective",),
    note="promise is on the EMITTED TOKEN STREAM: correction is near-exact "
         "on logits and the argmax absorbs the residual ulps, so drilled "
         "outputs are bit-identical to clean (tests/test_serve_drill.py)")
register_surface(
    "serve.engine/kv_cache_at_rest", owner=__name__, protected=True,
    promise="tolerance",
    detector="per-slot fingerprints (fp32 sums over the non-slot axes) "
             "verified before every decode step, plus a slot-sum checksum "
             "array per cache leaf: a tripped slot is rebuilt by the "
             "erasure solve ksum - sum(other slots); armed after every "
             "legitimate cache mutation (decode, admission scatter)",
    kinds=("dram_kv_cache",),
    note="single-slot fault model (one checksum row, like f=1 diskless); "
         "enabled via ServeEngine(scrub_every=N).  The same cadence "
         "verifies the params fingerprints and restores a tripped leaf "
         "from the held origin copy (stand-in for a checkpoint re-fetch)")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # host-side latency timeline (filled by the engine)
    t_submit: float = 0.0
    t_admit: float = 0.0     # admission started (queue wait = t_admit - t_submit)
    t_first: float = 0.0     # first token available (prefill done)
    t_done: float = 0.0

    @property
    def ttft_s(self) -> Optional[float]:
        """Time-to-first-token: submit -> prefill's argmax token."""
        return (self.t_first - self.t_submit) if self.t_first else None

    @property
    def decode_tok_s(self) -> Optional[float]:
        """Decode throughput for this request (tokens after the first)."""
        n = len(self.output) - 1
        dt = self.t_done - self.t_first
        return n / dt if (n > 0 and dt > 0) else None


@dataclasses.dataclass
class SDCEvent:
    """One fired SDC drill: what was injected and what the engine saw."""
    step: int                 # engine decode step the fault fired at
    shard: int                # model-axis shard whose contribution corrupts
    delta: float              # additive corruption (bit-flip magnitude)
    detected: bool = False
    corrected: bool = False
    row: int = -1             # located grid row/col inside the reduced leaf
    col: int = -1
    wall_s: float = 0.0       # wall time of the drilled step
    recovery_s: float = 0.0   # wall_s minus the mean clean step time


@dataclasses.dataclass
class ScrubEvent:
    """One at-rest scrub trip: where the flip was found and what fixed it."""
    step: int                 # engine decode step the verify ran at
    domain: str               # "kv" | "params"
    leaf: str                 # keystr of the tripped leaf
    slot: int = -1            # KV slot rebuilt (-1 for params / paged)
    page: int = -1            # physical page rebuilt (PagedServeEngine)
    repaired: bool = False
    wall_s: float = 0.0       # verify + repair wall


@dataclasses.dataclass
class EngineStats:
    """Per-engine step/FT accounting, reset by `ServeEngine.reset()`.

    detections/corrections count decode steps whose protected reduction
    reported an inconsistent / repaired checksum (drilled or not — a real
    SDC in the wild shows up here identically); `events` holds the fired
    drills with their located coordinates and recovery latency.
    """
    decode_steps: int = 0
    prefills: int = 0
    detections: int = 0
    corrections: int = 0
    prefill_s: float = 0.0           # total of the serve/prefill(_chunk) spans
    decode_s: float = 0.0            # total of the serve/decode_step spans
    decode_step_s: List[float] = dataclasses.field(default_factory=list)
    drilled_step_s: List[float] = dataclasses.field(default_factory=list)
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    tok_s: List[float] = dataclasses.field(default_factory=list)
    events: List[SDCEvent] = dataclasses.field(default_factory=list)
    scrub_checks: int = 0
    scrub_events: List[ScrubEvent] = dataclasses.field(default_factory=list)

    def clean_step_mean_s(self) -> float:
        xs = self.decode_step_s
        return sum(xs) / len(xs) if xs else 0.0

    def recovery_latency_s(self) -> float:
        """Mean extra wall time of detected-drill steps vs clean steps."""
        rs = [e.recovery_s for e in self.events if e.detected]
        return sum(rs) / len(rs) if rs else 0.0

    def summary(self) -> Dict[str, float]:
        mean = lambda xs: sum(xs) / len(xs) if xs else 0.0  # noqa: E731
        return {
            "decode_steps": self.decode_steps,
            "prefills": self.prefills,
            "detections": self.detections,
            "corrections": self.corrections,
            "prefill_s": self.prefill_s,
            "decode_s": self.decode_s,
            "clean_step_ms": 1e3 * self.clean_step_mean_s(),
            "drilled_step_ms": 1e3 * mean(self.drilled_step_s),
            "recovery_latency_ms": 1e3 * self.recovery_latency_s(),
            "ttft_ms": 1e3 * mean(self.ttft_s),
            "tok_per_s": mean(self.tok_s),
            "scrub_checks": self.scrub_checks,
            "scrub_repairs": sum(1 for e in self.scrub_events if e.repaired),
        }


_INFO0 = {"row": -1, "col": -1, "index": -1, "magnitude": 0.0,
          "corrected": False}


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 256, abft_mode: str = "off",
                 abft_backend: str = "auto", mesh: Optional[Mesh] = None,
                 abft_reduce: str = "off", abft_f: int = 2,
                 sdc: Optional[SDCInjector] = None, scrub_every: int = 0,
                 kernel_dtype: str = "fp32"):
        assert cfg.n_enc_layers == 0, "engine serves decoder-only archs"
        if abft_reduce not in ("off", "verify", "correct"):
            raise ValueError(f"unknown abft_reduce {abft_reduce!r}")
        if sdc is not None and abft_reduce == "off":
            raise ValueError("sdc drills corrupt the protected logits "
                             "reduction — set abft_reduce to 'verify' or "
                             "'correct'")
        obs_profiler.install()
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.abft_reduce = abft_reduce
        self.abft_f = abft_f
        self.sdc = sdc
        self._protected = abft_reduce != "off"
        self._warming = False
        # abft_backend="pallas" puts every protected projection of both
        # compiled programs (prefill_1, decode_B) on the fused dual-checksum
        # kernel; "auto" does so on TPU (see core.abft_gemm).
        # kernel_dtype narrows the protected-projection operand stream
        # (bf16 / int8 MXU rates); checksums stay fp32 with dtype-aware
        # detection eps, so the serving projections ride the mixed-
        # precision kernels without loosening the SDC promises.
        self.kernel_dtype = kernel_dtype
        self.abft = StepOptions(abft_mode=abft_mode,
                                abft_backend=abft_backend,
                                kernel_dtype=kernel_dtype).abft

        if mesh is None and self._protected:
            # the protected reduction needs a mesh axis to reduce over; a
            # 1-device mesh keeps one code path (psum over extent 1) and
            # still drills detection/correction end-to-end
            mesh = shd.make_mesh((1, 1), ("data", shd.MODEL_AXIS))
        self.mesh = mesh
        if self._protected:
            m_ext = mesh.shape.get(shd.MODEL_AXIS, 1)
            if shd.MODEL_AXIS not in mesh.axis_names:
                raise ValueError(
                    f"abft_reduce needs a '{shd.MODEL_AXIS}' mesh axis to "
                    f"reduce over (got axes {mesh.axis_names})")
            if cfg.d_model % m_ext:
                raise ValueError(
                    f"d_model={cfg.d_model} must divide over the model axis "
                    f"(extent {m_ext}) for the row-parallel verified unembed")
            if sdc is not None:
                # an out-of-range shard would be silently dropped by the
                # delta-vector scatter (jax OOB-scatter semantics) — the
                # drill would inject nothing and report detected=False
                bad = [e for e in sdc.plan.events if not 0 <= e[1] < m_ext]
                if bad:
                    raise ValueError(
                        f"SDC plan targets model-axis shards {sorted(e[1] for e in bad)} "
                        f"but the mesh's model extent is {m_ext}: the drill "
                        "would inject nothing (shard must be in "
                        f"[0, {m_ext}))")

        # shardings (identity placement when mesh is None)
        if mesh is not None:
            self._param_sh = shd.to_shardings(
                shd.infer_param_specs(params, mesh, cfg), mesh)
            self.params = jax.device_put(params, self._param_sh)
            self._rep = NamedSharding(mesh, P())
            bentry = shd.batch_specs(mesh, slots)[0]
            self._tok_sh = NamedSharding(mesh, P(bentry, None))
            self._pos_sh = NamedSharding(mesh, P(bentry))
            self._cache_sh = self._cache_shardings(slots)
        else:
            self.params = params
            self._param_sh = self._cache_sh = None
        self._info_struct = {k: jnp.asarray(v) for k, v in _INFO0.items()}

        self.active: List[Optional[Request]] = [None] * slots
        self.queue: Deque[Request] = deque()
        self.stats = EngineStats()
        self.cache = self._fresh_cache()
        self.pos = jnp.zeros((slots,), jnp.int32)
        self.tokens = jnp.zeros((slots, 1), jnp.int32)

        # at-rest scrub (serve.engine/kv_cache_at_rest + the serve side of
        # state.params_at_rest): `scrub_every` sets the verify cadence in
        # decode steps; arming (checksum-on-write) happens after every
        # legitimate cache mutation regardless.  Params are immutable while
        # serving, so they arm once: fingerprints for detection plus an
        # origin copy for repair (the stand-in for a checkpoint re-fetch).
        self.scrub_every = scrub_every
        self._kv_sums = {}
        self._param_fp = {}
        self._param_origin = None
        if scrub_every:
            self._param_fp = self._fingerprints(self.params)
            self._param_origin = jax.tree.map(
                lambda x: jnp.array(x, copy=True), self.params)
            self._arm_kv()

        if mesh is not None:
            in_sh = (self._param_sh, self._tok_sh, self._pos_sh,
                     self._cache_sh)
            out_sh = (self._rep, self._cache_sh, self._rep,
                      {k: self._rep for k in _INFO0})
            self._decode = jax.jit(self.serve_decode, in_shardings=in_sh,
                                   out_shardings=out_sh)
            self._decode_drill = jax.jit(
                self.serve_decode_drill,
                in_shardings=in_sh + (self._rep, self._rep),
                out_shardings=out_sh)
        else:
            self._decode = jax.jit(self.serve_decode)
            self._decode_drill = jax.jit(self.serve_decode_drill)
        self._prefill = {}  # len -> jitted prefill (bucketed)

    # -- public ---------------------------------------------------------------
    def submit(self, req: Request):
        if not req.t_submit:
            req.t_submit = time.perf_counter()
        self.queue.append(req)

    def run(self, max_steps: int = 10_000, on_step=None) -> List[Request]:
        """Drive until queue + slots drain; returns finished requests.

        ``on_step(engine, decode_step)`` — called before each decode step
        with the engine itself — is the chaos-campaign hook: a fault drill
        mutates engine state (flip a KV-cache or weight bit) mid-flight at
        a planned step; the engine re-places the mutated arrays before the
        compiled call as it always does."""
        finished: List[Request] = []
        for _ in range(max_steps):
            self._admit()
            if not any(self.active):
                if not self._pending():
                    break
                continue
            if on_step is not None:
                on_step(self, self.stats.decode_steps)
            self._step(finished)
        return finished

    def reset(self):
        """Clear serving state and stats; compiled programs are kept (the
        cheap way to reuse a warmed engine across benchmark phases)."""
        self.cache = self._fresh_cache()
        self.pos = jnp.zeros((self.slots,), jnp.int32)
        self.tokens = jnp.zeros((self.slots, 1), jnp.int32)
        self.active = [None] * self.slots
        self.queue = deque()
        self.stats = EngineStats()
        if self.scrub_every:
            self._arm_kv()

    def warm(self, prompt_len: int = 8, decode_steps: int = 2):
        """Warm BOTH compiled programs (the prefill bucket for `prompt_len`
        and decode_B) with a single dummy request — plus the drill variant
        of the decode program (injected delta 0.0 = no corruption) on
        engines that carry an SDC plan — then reset state and stats."""
        self._warming = True
        try:
            # +1: the prefill's argmax token is output[0], so max_new_tokens
            # = decode_steps + 1 yields exactly `decode_steps` decode steps
            self.submit(Request(rid=-1, prompt=[0] * prompt_len,
                                max_new_tokens=max(decode_steps, 1) + 1))
            self.run()
            if self._protected and self.sdc is not None:
                # only engines with a drill plan can ever invoke the drill
                # variant — don't compile a second decode program otherwise
                self._decode_drill(self.params, *self._place(),
                                   jnp.asarray(0, jnp.int32),
                                   jnp.asarray(0.0, jnp.float32))
        finally:
            self._warming = False
        self.reset()

    # -- internals --------------------------------------------------------------
    def _place(self):
        """(tokens, pos, cache) re-placed onto their program shardings.

        Host-side slot bookkeeping (`.at[s].set` scatters, eager argmax
        outputs) commits these arrays to whatever sharding the eager ops
        produced; pjit matches input shardings strictly, so re-place before
        every compiled call (no-op when already placed)."""
        if self.mesh is None:
            return self.tokens, self.pos, self.cache
        return (jax.device_put(self.tokens, self._tok_sh),
                jax.device_put(self.pos, self._pos_sh),
                jax.device_put(self.cache, self._cache_sh))

    def _fresh_cache(self):
        cache = tf.init_cache(self.cfg, self.slots, self.max_len)
        # force vector per-slot indices (init_cache makes scalars)
        cache = jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.zeros((x.shape[0], self.slots), jnp.int32)
            if (p and getattr(p[-1], "key", None) == "index") else x,
            cache)
        if self._cache_sh is not None:
            cache = jax.device_put(cache, self._cache_sh)
        return cache

    def _cache_shardings(self, batch: int):
        shapes = jax.eval_shape(
            lambda: tf.init_cache(self.cfg, batch, self.max_len))
        if batch == self.slots:  # engine cache carries VECTOR slot indices
            shapes = jax.tree_util.tree_map_with_path(
                lambda p, x: jax.ShapeDtypeStruct((x.shape[0], batch),
                                                  jnp.int32)
                if (p and getattr(p[-1], "key", None) == "index") else x,
                shapes)
        rule = shd.cache_specs(self.mesh, batch, self.cfg)
        specs = jax.tree_util.tree_map_with_path(rule, shapes)
        return shd.to_shardings(specs, self.mesh)

    def _bucket(self, n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _get_prefill(self, bucket: int):
        if bucket not in self._prefill:
            def serve_prefill(pr, tok, ln, _b=bucket):
                return self._prefill_impl(pr, tok, ln, _b)
            if self.mesh is not None:
                small_sh = self._cache_shardings(1)
                self._prefill[bucket] = jax.jit(
                    serve_prefill,
                    in_shardings=(self._param_sh, self._rep, self._rep),
                    out_shardings=(self._rep, small_sh))
            else:
                self._prefill[bucket] = jax.jit(serve_prefill)
        return self._prefill[bucket]

    # -- at-rest scrub ---------------------------------------------------------
    def _fingerprints(self, tree):
        """fp32 scalar sum per float leaf, keyed by keystr path (the cheap
        at-rest fingerprint for immutable state: the serving params)."""
        fps = {}
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if jnp.issubdtype(x.dtype, jnp.floating):
                fps[jax.tree_util.keystr(path)] = jnp.sum(
                    jnp.asarray(x, jnp.float32))
        return fps

    def _arm_kv(self):
        """Checksum-on-write for the KV cache: per-slot fingerprints
        (detect + locate the tripped slot) and a slot-sum checksum array
        (the erasure row that repairs it) per float cache leaf."""
        sums = {}
        for path, x in jax.tree_util.tree_flatten_with_path(self.cache)[0]:
            if (jnp.issubdtype(x.dtype, jnp.floating) and x.ndim >= 2
                    and x.shape[1] == self.slots):
                x32 = jnp.asarray(x, jnp.float32)
                fp = jnp.sum(x32, axis=tuple(range(2, x.ndim)))
                ks = jnp.sum(x32, axis=1)
                sums[jax.tree_util.keystr(path)] = (fp, ks)
        self._kv_sums = sums

    def _scrub_check(self):
        """Verify-on-read: recompute KV and params fingerprints against the
        armed values (split into `_scrub_kv` / `_scrub_params` so the paged
        engine can swap in its page-granular unit)."""
        step = self.stats.decode_steps
        events: List[ScrubEvent] = []
        with obs.span("serve/scrub", step=step) as sp:
            self.stats.scrub_checks += 1
            with obs.span("serve/scrub_kv", step=step):
                self._scrub_kv(step, events)
            with obs.span("serve/scrub_params", step=step):
                self._scrub_params(step, events)
        wall = sp.dur_s
        obs.histogram("repro_checksum_verify_seconds",
                      "at-rest scrub verify+repair wall").observe(
            wall, domain="serve")
        if events:
            for e in events:
                e.wall_s = wall
            self.stats.detections += len(events)
            self.stats.corrections += sum(1 for e in events if e.repaired)
            self.stats.scrub_events.extend(events)
            det = obs.counter("repro_detections_total",
                              "checksum/invariant trips")
            rep = obs.counter("repro_scrub_repairs_total",
                              "at-rest scrub repairs")
            for e in events:
                rung = ("scrub:page_repair" if e.page >= 0 else
                        "scrub:kv_repair" if e.domain == "kv" else
                        "scrub:restore")
                det.inc(surface="serve.scrub/" + e.domain)
                obs.event("fault/detect", step=step,
                          surface="serve.scrub/" + e.domain,
                          detector="fingerprint", leaf=e.leaf,
                          slot=e.slot, page=e.page)
                if e.repaired:
                    rep.inc(domain=e.domain)
                    obs.recovery(rung, wall, step=step, leaf=e.leaf,
                                 slot=e.slot, page=e.page)

    def _scrub_kv(self, step: int, events: List[ScrubEvent]):
        """A tripped KV slot is rebuilt by the erasure solve
        ``ksum - sum(other slots)`` (single-slot fault model, like f=1
        diskless)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.cache)
        leaves = []
        for path, x in flat:
            key = jax.tree_util.keystr(path)
            armed = self._kv_sums.get(key)
            if armed is not None:
                fp_a, ks_a = armed
                x32 = jnp.asarray(x, jnp.float32)
                fp = jnp.sum(x32, axis=tuple(range(2, x.ndim)))
                scale = float(jnp.max(jnp.abs(fp_a))) + 1.0
                diff = np.asarray(jnp.abs(fp - fp_a))
                # a flip into the NaN pattern poisons the slot sum; NaN
                # compares false against any threshold — count it tripped
                diff = np.where(np.isnan(diff), np.inf, diff)
                for s in sorted({int(b[1])
                                 for b in np.argwhere(diff > 1e-4 * scale)}):
                    # erasure solve over the SURVIVING slots only (zeroing
                    # the bad slot keeps a NaN/inf flip out of the sum)
                    live = jnp.sum(x32.at[:, s].set(0.0), axis=1)
                    x = x.at[:, s].set((ks_a - live).astype(x.dtype))
                    x32 = jnp.asarray(x, jnp.float32)
                    events.append(ScrubEvent(step=step, domain="kv",
                                             leaf=key, slot=s,
                                             repaired=True))
            leaves.append(x)
        if any(e.domain == "kv" for e in events):
            self.cache = jax.tree_util.tree_unflatten(treedef, leaves)

    def _scrub_params(self, step: int, events: List[ScrubEvent]):
        """A tripped params leaf is restored from the origin copy."""
        pflat, ptd = jax.tree_util.tree_flatten_with_path(self.params)
        oleaves = jax.tree.leaves(self._param_origin)
        pleaves = []
        dirty = False
        for (path, x), orig in zip(pflat, oleaves):
            key = jax.tree_util.keystr(path)
            fp_a = self._param_fp.get(key)
            if fp_a is not None:
                fp = jnp.sum(jnp.asarray(x, jnp.float32))
                d = float(jnp.abs(fp - fp_a))
                if np.isnan(d) \
                        or d > 1e-4 * (float(jnp.abs(fp_a)) + 1.0):
                    x = jnp.array(orig, copy=True)
                    dirty = True
                    events.append(ScrubEvent(step=step, domain="params",
                                             leaf=key, repaired=True))
            pleaves.append(x)
        if dirty:
            params = jax.tree_util.tree_unflatten(ptd, pleaves)
            if self._param_sh is not None:
                params = jax.device_put(params, self._param_sh)
            self.params = params

    # -- subclass hooks --------------------------------------------------------
    def _pending(self) -> bool:
        """Anything left to admit? (run()'s drain condition; the paged
        engine adds its scheduler queue and in-flight chunked prefill)."""
        return bool(self.queue)

    def _pre_decode(self):
        """Before each compiled decode call (paged engine: materialize the
        dense working cache from the page pools)."""

    def _post_decode(self):
        """After the decode mutated the cache, before positions advance.
        Contiguous engine: whole-cache re-arm — PR 6 granularity; the
        paged engine overrides this with per-page write-back + re-arm."""
        if self.scrub_every and not self._warming:
            self._arm_kv()  # re-arm: the decode mutated every live slot

    def _retire_slot(self, s: int):
        """A slot's request just finished (paged engine frees its pages)."""

    def _writeback_pages(self, n_active: int) -> int:
        """One-token page writes `_post_decode` makes for ``n_active``
        slots (the contiguous engine writes no pages: it re-arms the whole
        cache)."""
        return 0

    def _write_pages(self, s: int, cache_tree, start: int, end: int):
        """Persist positions [start, end) of a 1-slot prefill cache (paged
        engine; the contiguous engine's slot scatter already holds them)."""

    def _admit(self):
        admitted = False
        for s in range(self.slots):
            if self.active[s] is not None or not self.queue:
                continue
            self._start_admission(s, self.queue.popleft())
            admitted = True
        if admitted and self.scrub_every and not self._warming:
            self._arm_kv()  # re-arm after the admission scatter

    def _admission(self, req: Request, prefix_tokens: int = 0):
        """The ``serve/admit`` span of one admission, which starts now;
        observes the request's queue wait (submit -> admission)."""
        req.t_admit = time.perf_counter()
        wait = req.t_admit - req.t_submit
        if not self._warming:
            obs.histogram("repro_queue_wait_seconds",
                          "queue wait from submit to admission").observe(wait)
        return obs.span("serve/admit", rid=req.rid, queue_wait_ms=1e3 * wait,
                        prompt_len=len(req.prompt),
                        prefix_tokens=prefix_tokens)

    def _start_admission(self, s: int, req: Request):
        with self._admission(req):
            tok = self._prefill_whole(s, req)
            self._finish_admission(s, req, tok, len(req.prompt))

    def _prefill_whole(self, s: int, req: Request) -> int:
        """Prefill the whole prompt in one compiled program into slot
        ``s``; returns the first token."""
        plen = len(req.prompt)
        bucket = self._bucket(plen)
        with obs.span("serve/prefill", rid=req.rid, bucket=bucket) as sp:
            prompt = jnp.zeros((1, bucket), jnp.int32).at[0, :plen].set(
                jnp.asarray(req.prompt, jnp.int32))
            logits, small_cache = self._get_prefill(bucket)(
                self.params, prompt, jnp.asarray(plen, jnp.int32))
            self._scatter_slot(s, small_cache, plen)
            self._write_pages(s, small_cache, 0, plen)
            tok = int(jnp.argmax(logits[0, plen - 1]))
        self.stats.prefill_s += sp.dur_s
        return tok

    def _finish_admission(self, s: int, req: Request, tok: int, plen: int):
        req.output.append(tok)
        req.t_first = time.perf_counter()
        self.stats.prefills += 1
        self.tokens = self.tokens.at[s, 0].set(tok)
        self.pos = self.pos.at[s].set(plen)
        self.active[s] = req

    def _prefill_impl(self, params, prompt, plen, bucket):
        cache = tf.init_cache(self.cfg, 1, self.max_len)
        logits, new_cache, _ = tf.forward(params, prompt, self.cfg,
                                          cache=cache, abft=self.abft)
        return logits, new_cache

    def _scatter_slot(self, s: int, small_cache, plen: int):
        def put(path, big, small):
            key = getattr(path[-1], "key", None)
            if key == "index":
                return big.at[..., s].set(plen)
            # leading dims: [repeats, B(slots), ...] <- [repeats, 1, ...]
            return big.at[:, s].set(small[:, 0].astype(big.dtype))

        self.cache = jax.tree_util.tree_map_with_path(
            lambda p, b, sm: put(p, b, sm), self.cache, small_cache)

    # -- decode programs -------------------------------------------------------
    # the jitted programs take these names (the trace's "XLA Modules" line
    # shows jit_serve_decode, jit_serve_decode_drill)
    def serve_decode(self, params, tokens, pos, cache):
        return self._decode_core(params, tokens, pos, cache, None)

    def serve_decode_drill(self, params, tokens, pos, cache, shard, delta):
        return self._decode_core(params, tokens, pos, cache, (shard, delta))

    def _decode_core(self, params, tokens, pos, cache, inject):
        if not self._protected:
            logits, new_cache = tf.decode_step(params, tokens, pos, cache,
                                               self.cfg, abft=self.abft)
            return (logits, new_cache, jnp.asarray(True),
                    dict(self._info_struct))
        hidden, new_cache = tf.decode_step(params, tokens, pos, cache,
                                           self.cfg, abft=self.abft,
                                           return_hidden=True)
        logits, ok, info = self._verified_unembed(params, hidden, inject)
        return logits, new_cache, ok, info

    def _verified_unembed(self, params, x, inject):
        """Row-parallel final projection with the cross-shard reduction
        checksum-verified (and drill-injectable) via `abft_psum`.

        x: [B, 1, D] post-final-norm hidden.  Each model shard computes the
        partial logits of its D/m feature slice; `abft_psum` reduces the
        partials over the "model" axis with Huang-Abraham checksums riding
        the SAME collective, detecting (and in "correct" mode repairing) a
        single corrupted element of the reduction in-flight.
        """
        head = params.get("lm_head")
        w = head["w"] if head is not None else params["embed"]["table"]
        # lm_head w: [D, V] -> split contraction dim; tied embedding table:
        # [V, D] -> split feature dim and transpose inside the region
        wspec = (P(shd.MODEL_AXIS, None) if head is not None
                 else P(None, shd.MODEL_AXIS))
        mode, f = self.abft_reduce, self.abft_f

        def local(w_l, x_l, *inj):
            wl = w_l.astype(jnp.float32)
            if head is None:
                wl = wl.T                                  # [D/m, V]
            part = jnp.einsum("bsd,dv->bsv",
                              x_l.astype(jnp.float32), wl)
            # inj, when present, is this shard's [1] slice of the delta
            # vector — shard selection happened OUTSIDE the region, so no
            # axis_index is needed (see chaos.faults.scatter_delta)
            return abft_psum(part, (shd.MODEL_AXIS,), f=f, mode=mode,
                             inject_local=inj[0][0] if inj else None,
                             with_info=True)

        in_specs = (wspec, P(None, None, shd.MODEL_AXIS))
        args = (w, x)
        if inject is not None:
            shard, delta = inject
            dvec = scatter_delta(self.mesh.shape[shd.MODEL_AXIS], shard,
                                 delta)
            in_specs += (P(shd.MODEL_AXIS),)
            args += (dvec,)
        out_specs = (P(None, None, None), P(), {k: P() for k in _INFO0})
        y, ok, info = jax.shard_map(
            local, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False, axis_names=frozenset({shd.MODEL_AXIS}))(*args)
        if head is not None and "b" in head:
            y = y + head["b"].astype(jnp.float32)
        y = softcap_fn(y, self.cfg.final_softcap)
        return y[:, -1], ok, info

    # -- step ------------------------------------------------------------------
    def _step(self, finished: List[Request]):
        if (self.scrub_every and not self._warming
                and self.stats.decode_steps % self.scrub_every == 0):
            self._scrub_check()
        n_active = sum(r is not None for r in self.active)
        with obs.span("serve/kv_gather", active=n_active):
            self._pre_decode()
        ev: Optional[SDCEvent] = None
        with obs.span("serve/decode_step", active=n_active) as sp:
            if self.sdc is not None and not self._warming:
                fired = self.sdc.check(self.stats.decode_steps)
                if fired is not None:
                    shard, delta = fired
                    ev = SDCEvent(step=self.stats.decode_steps, shard=shard,
                                  delta=delta)
            tokens, pos, cache = self._place()
            if ev is not None:
                logits, self.cache, ok, info = self._decode_drill(
                    self.params, tokens, pos, cache,
                    jnp.asarray(ev.shard, jnp.int32),
                    jnp.asarray(ev.delta, jnp.float32))
            else:
                logits, self.cache, ok, info = self._decode(
                    self.params, tokens, pos, cache)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            jax.block_until_ready(next_tok)
        wall = sp.dur_s

        detected = self._protected and not bool(ok)
        step = self.stats.decode_steps
        self.stats.decode_steps += 1
        self.stats.decode_s += wall
        if not self._warming:
            obs.counter("repro_decode_steps_total",
                        "engine decode steps").inc()
        if detected:
            self.stats.detections += 1
            if bool(info["corrected"]):
                self.stats.corrections += 1
        if ev is not None:
            ev.detected = detected
            ev.corrected = bool(info["corrected"])
            ev.row, ev.col = int(info["row"]), int(info["col"])
            ev.wall_s = wall
            base = self.stats.clean_step_mean_s()
            ev.recovery_s = max(wall - base, 0.0) if base else 0.0
            self.stats.drilled_step_s.append(wall)
            self.stats.events.append(ev)
            obs.event("fault/inject", step=step,
                      surface="serve.engine/logits_reduce",
                      kind="sdc_reduce", shard=ev.shard, delta=ev.delta)
        else:
            self.stats.decode_step_s.append(wall)
        if detected:
            obs.counter("repro_detections_total",
                        "checksum/invariant trips").inc(
                surface="serve.engine/logits_reduce")
            obs.event("fault/detect", step=step,
                      surface="serve.engine/logits_reduce",
                      detector="abft_psum",
                      row=int(info["row"]), col=int(info["col"]))
            if bool(info["corrected"]):
                obs.counter("repro_corrections_total",
                            "in-flight ABFT corrections").inc()
            rec = ev.recovery_s if ev is not None else wall
            # the correct-path lives inside the already-traced decode
            # program, so even the first detection's wall is compile-free
            obs.recovery("abft_inflight", rec, step=step, warm_s=rec,
                         compile_s=0.0, corrected=bool(info["corrected"]))
        pages = self._writeback_pages(n_active)
        with obs.span("serve/kv_writeback", pages=pages,
                      host_reads=2 * pages):
            self._post_decode()
        with obs.span("serve/emit", active=n_active):
            self._emit(next_tok, finished)

    def _emit(self, next_tok, finished: List[Request]):
        """Append each live slot's token, advance positions and retire the
        finished requests."""
        self.pos = self.pos + jnp.asarray(
            [1 if r is not None else 0 for r in self.active], jnp.int32)
        self.tokens = next_tok[:, None]
        now = time.perf_counter()
        for s, req in enumerate(self.active):
            if req is None:
                continue
            tok = int(next_tok[s])
            req.output.append(tok)
            hit_eos = req.eos_id is not None and tok == req.eos_id
            if len(req.output) >= req.max_new_tokens or hit_eos \
                    or int(self.pos[s]) >= self.max_len - 1:
                req.done = True
                req.t_done = now
                if req.ttft_s is not None:
                    self.stats.ttft_s.append(req.ttft_s)
                    obs.histogram("repro_ttft_seconds",
                                  "time to first token").observe(req.ttft_s)
                if req.decode_tok_s is not None:
                    self.stats.tok_s.append(req.decode_tok_s)
                obs.counter("repro_requests_total",
                            "retired serve requests").inc()
                finished.append(req)
                self._retire_slot(s)
                self.active[s] = None


class PagedServeEngine(ServeEngine):
    """`ServeEngine` on a paged/block KV cache (serve.paged_kv) with prefix
    caching, chunked prefill, and an optional SLO-aware scheduler.

    The page pools are the AUTHORITATIVE storage: every decode step gathers
    them into the fixed-shape dense cache the inherited compiled programs
    consume (`_pre_decode`), and writes each slot's freshly decoded K/V
    back into its page afterwards (`_post_decode`) — re-arming exactly the
    pages it touched instead of the whole cache (the PR 6 scrub-unit fix).
    A retiring slot frees its pages (zero-at-free), and the at-rest scrub
    verifies/repairs at page granularity via the pool's erasure sum.

    Decode parity: with ``chunk_prefill=0`` and no prefix hit, admission
    runs the parent's compiled prefill program verbatim and the gathered
    dense cache differs from the contiguous engine's only at causally
    masked positions (zeros vs prefill pad garbage) — decode logits, and
    therefore the emitted token streams, are bit-identical
    (tests/test_traffic.py).  Chunked and prefix-shared prefills change
    the prefill computation's shape, so their guarantee is on the argmax
    token stream, not logits bits.

    ``scheduler``: an `SLOScheduler` (serve.scheduler) takes over queueing
    — `submit()` routes through its admission control (rejections land in
    ``self.rejected``) and `_admit` pops by aged effective priority.
    ``chunk_prefill=C``: prompts longer than C prefill C tokens per engine
    step, so a long prompt never delays a running decode step by more than
    one chunk's work (tests/test_scheduler.py); when no decode is active
    the chunks free-run back-to-back.
    """

    def __init__(self, cfg: ModelConfig, params, *, page_size: int = 8,
                 chunk_prefill: int = 0, prefix_cache: bool = True,
                 scheduler=None, max_prefixes: int = 16, **kw):
        from repro.serve.paged_kv import PagedKVCache  # noqa: F401 (type)
        self.page_size = page_size
        self.chunk_prefill = chunk_prefill
        self.prefix_cache = prefix_cache
        self.max_prefixes = max_prefixes
        self.scheduler = scheduler
        self.kv = None                    # built by _fresh_cache
        self.rejected: List[Request] = []
        self._prefilling: Optional[dict] = None
        self._chunk_progs = {}
        super().__init__(cfg, params, **kw)

    # -- paged storage ---------------------------------------------------------
    def _fresh_cache(self):
        from repro.serve.paged_kv import PagedKVCache
        dense = super()._fresh_cache()
        shapes = {}
        for path, x in jax.tree_util.tree_flatten_with_path(dense)[0]:
            # paged leaves: per-slot sequence-indexed float K/V, i.e.
            # [repeats, slots, max_len, *tail]; recurrent state (mamba,
            # xLSTM) has no max_len axis and stays dense-only
            if (jnp.issubdtype(x.dtype, jnp.floating) and x.ndim >= 4
                    and x.shape[1] == self.slots
                    and x.shape[2] == self.max_len):
                shapes[jax.tree_util.keystr(path)] = (x.shape, x.dtype)
        self.kv = PagedKVCache(shapes, slots=self.slots,
                               max_len=self.max_len,
                               page_size=self.page_size,
                               max_prefixes=self.max_prefixes)
        return dense

    def _arm_kv(self):
        self.kv.arm_all()

    def _scrub_kv(self, step: int, events: List[ScrubEvent]):
        for key, page in self.kv.scrub():
            events.append(ScrubEvent(step=step, domain="kv", leaf=key,
                                     page=page, repaired=True))

    def _pre_decode(self):
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.cache)
        leaves = []
        for path, x in flat:
            key = jax.tree_util.keystr(path)
            leaves.append(self.kv.gather(key) if key in self.kv.pools else x)
        self.cache = jax.tree_util.tree_unflatten(treedef, leaves)

    def _post_decode(self):
        # page-granular write-back + re-arm: ONE page per leaf per active
        # slot (the decode wrote exactly position pos[s])
        writes = [(s, int(p)) for s, (r, p) in
                  enumerate(zip(self.active, np.asarray(self.pos)))
                  if r is not None]
        if not writes:
            return
        self.kv.begin_mutation()
        for path, x in jax.tree_util.tree_flatten_with_path(self.cache)[0]:
            key = jax.tree_util.keystr(path)
            if key not in self.kv.pools:
                continue
            for s, p in writes:
                self.kv.write_token(key, s, p, x[:, s, p])

    def _writeback_pages(self, n_active: int) -> int:
        return n_active * len(self.kv.pools)

    def _retire_slot(self, s: int):
        self.kv.free_slot(s)

    # -- queueing / admission --------------------------------------------------
    def submit(self, req: Request, priority: Optional[int] = None):
        if not req.t_submit:
            req.t_submit = time.perf_counter()
        if self.scheduler is not None and not self._warming:
            if not self.scheduler.submit(req, priority=priority):
                req.done = True
                self.rejected.append(req)
            return
        self.queue.append(req)

    def reset(self):
        super().reset()          # rebuilds self.kv via _fresh_cache
        self.rejected = []
        self._prefilling = None

    def _pending(self) -> bool:
        return (bool(self.queue) or self._prefilling is not None
                or (self.scheduler is not None and len(self.scheduler) > 0))

    def _next_request(self) -> Optional[Request]:
        if self.scheduler is not None:
            req = self.scheduler.pop()
            if req is not None:
                return req
        return self.queue.popleft() if self.queue else None

    def _admit(self):
        if self._prefilling is not None:
            self._advance_prefill()
            if self._prefilling is not None:
                return          # one chunk per engine step under decode load
        while True:
            free = [s for s in range(self.slots) if self.active[s] is None]
            if not free:
                return
            req = self._next_request()
            if req is None:
                return
            self._start_admission(free[0], req)
            if self._prefilling is not None:
                self._advance_prefill()   # free-runs when no decode active
                if self._prefilling is not None:
                    return

    def _start_admission(self, s: int, req: Request):
        plen = len(req.prompt)
        need = min(plen + req.max_new_tokens, self.max_len)
        shared = (self.kv.lookup_prefix(req.prompt)
                  if self.prefix_cache and not self._warming else [])
        with self._admission(req, prefix_tokens=len(shared) * self.page_size):
            start = self.kv.alloc_slot(s, need, shared=shared)
            if start or (self.chunk_prefill and plen > self.chunk_prefill):
                self._prefilling = {"slot": s, "req": req, "start": start}
                return
            # no prefix hit, no chunking: the parent's compiled prefill
            # program verbatim — bit-identical admission vs the contiguous
            # engine
            tok = self._prefill_whole(s, req)
            self._finish_admission(s, req, tok, plen)

    def _finish_admission(self, s: int, req: Request, tok: int, plen: int):
        super()._finish_admission(s, req, tok, plen)
        if self.prefix_cache and not self._warming:
            self.kv.register_prefix(s, req.prompt)

    def _write_pages(self, s: int, cache_tree, start: int, end: int):
        """Persist positions [start, end) of a 1-slot cache into pages."""
        if end <= start:
            return
        ps = self.page_size
        pages = len(self.kv.pools) * (-(-end // ps) - start // ps)
        with obs.span("serve/kv_write", pages=pages, host_reads=2 * pages):
            self.kv.begin_mutation()
            for path, x in jax.tree_util.tree_flatten_with_path(
                    cache_tree)[0]:
                key = jax.tree_util.keystr(path)
                if key in self.kv.pools:
                    self.kv.write(key, s, start, x[:, 0, start:end])

    # -- chunked / prefix-shared prefill --------------------------------------
    def _slot_cache(self, s: int, start: int):
        """Dense 1-slot cache for the chunk program: this slot's pages
        gathered, non-paged leaves sliced from the engine cache, block
        indices set to the chunk's start position."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.cache)
        leaves = []
        for path, x in flat:
            key = jax.tree_util.keystr(path)
            if key in self.kv.pools:
                leaves.append(self.kv.gather_slot(key, s))
            elif getattr(path[-1], "key", None) == "index":
                leaves.append(jnp.full((x.shape[0],), start, jnp.int32))
            elif x.ndim >= 2 and x.shape[1] == self.slots:
                leaves.append(x[:, s:s + 1])
            else:
                leaves.append(x)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _get_chunk(self, bucket: int):
        if bucket not in self._chunk_progs:
            def serve_prefill_chunk(pr, tok, start, cache, _b=bucket):
                positions = start + jnp.arange(_b)
                logits, new_cache, _ = tf.forward(
                    pr, tok, self.cfg, positions=positions, cache=cache,
                    abft=self.abft)
                return logits, new_cache
            self._chunk_progs[bucket] = jax.jit(serve_prefill_chunk)
        return self._chunk_progs[bucket]

    def _advance_prefill(self):
        """Process chunks of the in-flight prefill: one chunk when any
        decode is running (the chunk budget is the most a decode step can
        be delayed), back-to-back when the engine is otherwise idle."""
        while self._prefilling is not None:
            pf = self._prefilling
            s, req, start = pf["slot"], pf["req"], pf["start"]
            plen = len(req.prompt)
            n = min(self.chunk_prefill or plen - start, plen - start)
            bucket = self._bucket(n)
            with obs.span("serve/prefill_chunk", rid=req.rid, start=start,
                          n=n) as sp:
                toks = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
                    jnp.asarray(req.prompt[start:start + n], jnp.int32))
                cache = self._slot_cache(s, start)
                logits, new_cache = self._get_chunk(bucket)(
                    self.params, toks, jnp.asarray(start, jnp.int32), cache)
                # carry non-paged leaves (recurrent state) + index across
                # chunks
                self._scatter_slot(s, new_cache, start + n)
                self._write_pages(s, new_cache, start, start + n)
                pf["start"] = start + n
            self.stats.prefill_s += sp.dur_s
            if pf["start"] >= plen:
                tok = int(jnp.argmax(logits[0, n - 1]))
                self._prefilling = None
                self._finish_admission(s, req, tok, plen)
                return
            if any(r is not None for r in self.active):
                return
