"""Train / prefill / serve step builders with full sharding annotations.

`build_train_step` assembles: microbatched gradient accumulation (lax.scan),
remat, fp32 grad accumulation, global-norm clipping, AdamW (+ZeRO-1 state
sharding), optional ABFT weight-checksum protection of every projection, and
optional error-feedback gradient compression of the DP reduction.

All builders return (fn, in_shardings, out_shardings, example_inputs) so the
launcher and the dry-run share one code path.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.abft_gemm import ABFTConfig
from repro.dist import sharding as shd
from repro.models import transformer as tf
from repro.models.moe import MOE_STATS
from repro.train.optimizer import (AdamWConfig, adamw_init, adamw_opt_specs,
                                   adamw_update)

__all__ = ["StepOptions", "build_train_step", "build_serve_step",
           "build_prefill_step", "make_inputs", "init_state"]


@dataclasses.dataclass(frozen=True)
class StepOptions:
    microbatches: int = 1
    remat: bool = True
    zero1: bool = True
    abft_mode: str = "off"         # off | checksum | verify | correct
    abft_f: int = 2
    # matmul-ABFT backend: "pallas" routes the protected projections through
    # the fused dual-checksum kernel (kernels.ops), "ref" keeps plain XLA,
    # "auto" fuses on TPU (core.abft_gemm dispatch).
    abft_backend: str = "auto"
    # operand dtype for the ABFT-protected projections: "fp32" | "bf16" |
    # "int8".  Narrows only the GEMM A/B stream (checksums stay fp32 with
    # dtype-aware detection eps — core.abft_gemm); int8 composes with the
    # grad_compression="int8_ef" wire for the end-to-end low-precision run.
    kernel_dtype: str = "fp32"
    grad_compression: str = "none"  # none | int8_ef
    # weight of `models.moe.balance_loss`, E * sum_e f_e P_e with the f_e
    # summing to top_k (the published form): a weight tuned for f_e
    # summing to 1 is top_k times larger here
    aux_weight: float = 0.01
    # defer the DP gradient all-reduce to AFTER microbatch accumulation
    # (shard_map manual-DP region: one psum instead of one per microbatch —
    # cuts grad collective bytes by the microbatch count).
    # The defer family (defer / zero2 / int8_ef / abft_reduce) is driven
    # single-device: an earlier toolchain's SPMD partitioner aborted
    # (IsManualSubgroup) on lax.scan-over-stacked-params inside a PARTIAL-
    # manual region, and multi-device is not yet re-tested on jax 0.9
    # (ROADMAP Queue 3, item 2); tests exercise single-device SPMD and the
    # vmap collective semantics.
    defer_grad_reduce: bool = False
    # ZeRO-2: reduce-SCATTER the deferred gradients over DP (each device
    # holds 1/ndp of the fp32 grads, matching the ZeRO-1 opt-state shards;
    # params re-gather after the update).  Requires defer_grad_reduce.
    zero2: bool = False
    # remat policy for the layer scan: True/"nothing" = save nothing
    # (min memory, max recompute); "dots" = save matmul outputs
    # (recompute only elementwise; ~1.3x less compute, more memory)
    remat_policy: str = "nothing"
    # FSDP: shard the PARAMS over DP too (zero-dim rule, same as the ZeRO-1
    # opt state).  XLA all-gathers weights at use inside the layer scan and
    # reduce-scatters grads — ZeRO-3 semantics via sharding rules alone.
    # Required to FIT kimi-1T / jamba-398B on the 256-chip mesh.
    fsdp: bool = False
    # checksum-protect the DP gradient all-reduce itself (Huang-Abraham row
    # rides the same psum — dist.collectives.abft_psum).  "verify" detects a
    # corrupted reduction (metrics["abft_ok"]), "correct" repairs a single
    # corrupted element.  Takes effect on the defer_grad_reduce path.
    abft_reduce: str = "off"       # off | verify | correct
    # FT drill hook: (dp_shard, delta) corrupts one gradient element of that
    # shard's contribution DURING the reduction (after its checksum is
    # taken) — lets ft.runtime exercise detection/correction end-to-end.
    # Also accepts a TUPLE of such pairs: event j then lands in the j-th
    # protected reduction of the step (multi-collective fault drills).
    sdc_inject: Optional[Tuple] = None
    # run the models.layers construction invariants inside the forward
    # (embedding-gather checksum column, every rmsnorm second moment) and
    # surface the AND of all checks as metrics["inv_ok"].  Rides the
    # standard grad path only — the deferred manual-DP region does not
    # thread the flags (raises when combined with defer_grad_reduce).
    invariant_checks: bool = False

    @property
    def remat_arg(self):
        if not self.remat:
            return False
        return "dots" if self.remat_policy == "dots" else True

    @property
    def abft(self) -> Optional[ABFTConfig]:
        if self.abft_mode == "off":
            return None
        return ABFTConfig(mode=self.abft_mode, f=self.abft_f,
                          backend=self.abft_backend,
                          in_dtype=self.kernel_dtype)


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


def make_inputs(cfg: ModelConfig, shape: ShapeConfig, *, structs: bool = True):
    """ShapeDtypeStruct stand-ins (or zeros) for every model input."""
    b, s = shape.global_batch, shape.seq_len
    mk = (lambda sh, dt: jax.ShapeDtypeStruct(sh, dt)) if structs else \
         (lambda sh, dt: jnp.zeros(sh, dt))
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        out["tokens"] = mk((b, s), jnp.int32)
        out["labels"] = mk((b, s), jnp.int32)
    elif shape.kind == "prefill":
        out["tokens"] = mk((b, s), jnp.int32)
    else:  # decode
        out["tokens"] = mk((b, 1), jnp.int32)
        out["pos"] = mk((), jnp.int32)
    if cfg.n_enc_layers and shape.kind != "decode":
        out["frames"] = mk((b, cfg.n_frames, cfg.d_model), dt)
    if cfg.n_img_tokens:
        out["img_emb"] = mk((b, cfg.n_img_tokens, cfg.d_model), dt)
    return out


def _input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    bspec = shd.batch_specs(mesh, shape.global_batch)
    specs: Dict[str, Any] = {}
    inputs = make_inputs(cfg, shape)
    for k, v in inputs.items():
        if k == "pos":
            specs[k] = P()
        else:
            specs[k] = P(*(list(bspec) + [None] * (v.ndim - 1)))
    return specs


def _moe_cfg(cfg: ModelConfig, mesh: Mesh) -> ModelConfig:
    """Set MoE dispatch groups to the DP extent for device-local sort."""
    if not cfg.n_experts:
        return cfg
    dp = 1
    for a in shd.dp_axes(mesh):
        dp *= mesh.shape[a]
    return cfg.scaled(moe_groups=dp)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------


def init_state(key, cfg: ModelConfig, opts: StepOptions, mesh: Mesh = None):
    params = tf.init_params(key, cfg)
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    if opts.grad_compression == "int8_ef":
        # per-DP-shard error-feedback residuals (leading dim = DP extent)
        ndp = 1
        if mesh is not None:
            for a in shd.dp_axes(mesh):
                ndp *= mesh.shape[a]
        state["ef_residual"] = jax.tree.map(
            lambda p: jnp.zeros((ndp,) + p.shape, jnp.float32), params)
    return state


def state_specs(state_shapes, mesh: Mesh, opts: StepOptions, cfg=None):
    """Mesh-agnostic PartitionSpec tree for a whole train state.

    Param rules come from `dist.sharding`, optimizer-state rules from the
    optimizer itself (`adamw_opt_specs`) — no layer hardcodes another's
    state structure, which is what lets `ckpt.elastic` re-place params AND
    ZeRO-1 opt state onto a survivor mesh with one call.
    """
    pspecs = shd.infer_param_specs(state_shapes["params"], mesh, cfg)
    if opts.fsdp:
        # params themselves carry the DP sharding (weights all-gather at
        # use; grads reduce-scatter) — ZeRO-3 via pjit rules.  The opt
        # state shares the (already maximal) param sharding.
        pspecs = jax.tree_util.tree_map_with_path(
            lambda path, s: shd.zero1_spec(
                s, _lookup(state_shapes["params"], path).shape, mesh),
            pspecs)
        opt = adamw_opt_specs(pspecs)
    else:
        opt = adamw_opt_specs(pspecs, state_shapes["params"], mesh,
                              zero1=opts.zero1)
    out = {
        "params": pspecs,
        "opt": opt,
        "step": P(),
    }
    if "ef_residual" in state_shapes:
        dp = shd.dp_axes(mesh)
        dp_spec = dp if len(dp) > 1 else dp[0]
        out["ef_residual"] = jax.tree.map(
            lambda s: P(*((dp_spec,) + tuple(s))), pspecs,
            is_leaf=lambda x: isinstance(x, P))
    return out


def _lookup(tree, path):
    node = tree
    for p in path:
        key = getattr(p, "key", getattr(p, "idx", None))
        node = node[key]
    return node


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------


def build_train_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
                     adamw: AdamWConfig = AdamWConfig(),
                     opts: StepOptions = StepOptions()):
    """Returns (step_fn, in_shardings, donate_argnums)."""
    if opts.abft_reduce != "off" and (
            not opts.defer_grad_reduce or opts.zero2
            or opts.grad_compression != "none"):
        raise ValueError(
            "abft_reduce protects the deferred DP all-reduce: it requires "
            "defer_grad_reduce=True and is incompatible with zero2 / "
            f"grad_compression (got {opts})")
    if opts.sdc_inject is not None and opts.abft_reduce == "off":
        raise ValueError("sdc_inject corrupts the protected reduction — "
                         "set abft_reduce to 'verify' or 'correct'")
    if opts.invariant_checks and opts.defer_grad_reduce:
        raise ValueError("invariant_checks rides the standard grad path; "
                         "the deferred manual-DP region does not thread "
                         "the invariant flags")
    cfg = _moe_cfg(cfg, mesh)
    m = opts.microbatches
    assert shape.global_batch % max(m, 1) == 0
    bspec = shd.batch_specs(mesh, shape.global_batch // max(m, 1))
    logits_sharding = NamedSharding(
        mesh, P(*(list(bspec)
                  + [None, "model" if cfg.vocab_size % mesh.shape["model"] == 0
                     else None])))
    x_sharding = NamedSharding(mesh, P(*(list(bspec) + [None, None])))
    batch_sharding = NamedSharding(mesh, P(*bspec))

    inv_on = opts.invariant_checks
    # the held-expert layer's counters (models.moe.MOE_STATS) ride the
    # loss's has_aux output on the standard grad path
    moe_on = bool(cfg.experts_held) and not opts.defer_grad_reduce
    has_extras = inv_on or moe_on

    def loss_of(params, batch):
        batch = dict(batch,
                     tokens=jax.lax.with_sharding_constraint(
                         batch["tokens"], batch_sharding),
                     labels=jax.lax.with_sharding_constraint(
                         batch["labels"], batch_sharding))
        return tf.loss_fn(
            params, batch["tokens"], batch["labels"], cfg,
            frames=batch.get("frames"), img_emb=batch.get("img_emb"),
            abft=opts.abft, remat=opts.remat_arg, aux_weight=opts.aux_weight,
            logits_sharding=logits_sharding, x_sharding=x_sharding,
            invariants=inv_on, moe_stats=moe_on)

    def _merge_extras(a, b):
        out = {}
        if "inv_ok" in a:
            out["inv_ok"] = a["inv_ok"] & b["inv_ok"]
        if "moe" in a:
            out["moe"] = tf.merge_moe_stats(a["moe"], b["moe"])
        return out

    def _accumulate(loss_fn_, params, batch):
        """Microbatch scan accumulating fp32 grads (no reduction choices).

        Returns (loss, grads), or (loss, grads, extras) when the loss fn
        carries invariant flags or MoE counters (has_aux form)."""
        vg = jax.value_and_grad(loss_fn_, has_aux=has_extras)
        if m <= 1:
            if has_extras:
                (loss, ex), grads = vg(params, batch)
                return loss, grads, ex
            return vg(params, batch)

        def split(x):
            return x.reshape((m, x.shape[0] // m) + x.shape[1:])
        mbatch = jax.tree.map(split, batch)

        def acc_step(carry, mb):
            loss_acc, g_acc, ex_acc = carry
            if has_extras:
                (l, ex_mb), g = vg(params, mb)
                ex_acc = _merge_extras(ex_acc, ex_mb)
            else:
                l, g = vg(params, mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (loss_acc + l, g_acc, ex_acc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        ex0 = {}
        if inv_on:
            ex0["inv_ok"] = jnp.array(True)
        if moe_on:
            ex0["moe"] = jnp.zeros((len(MOE_STATS),), jnp.float32)
        (loss, grads, ex), _ = lax.scan(
            acc_step, (jnp.zeros(()), g0, ex0), mbatch)
        loss, grads = loss / m, jax.tree.map(lambda g: g / m, grads)
        return (loss, grads, ex) if has_extras else (loss, grads)

    if opts.defer_grad_reduce:
        dp = shd.dp_axes(mesh)
        # inside the manual-DP region: batch is the LOCAL shard; the model
        # constraints may only reference auto axes
        local_logits_sh = NamedSharding(
            mesh, P(None, None,
                    "model" if cfg.vocab_size % mesh.shape["model"] == 0
                    else None))
        local_cfg = cfg.scaled(moe_groups=1) if cfg.n_experts else cfg

        def local_loss(params, batch):
            return tf.loss_fn(
                params, batch["tokens"], batch["labels"], local_cfg,
                frames=batch.get("frames"), img_emb=batch.get("img_emb"),
                abft=opts.abft, remat=opts.remat_arg, aux_weight=opts.aux_weight,
                logits_sharding=local_logits_sh)

        ndp = 1
        for a in dp:
            ndp *= mesh.shape[a]
        compress = opts.grad_compression == "int8_ef"
        ispecs_local = _input_specs(cfg, shape, mesh)
        params_specs = jax.tree.map(
            lambda _: P(),
            jax.eval_shape(lambda k: tf.init_params(k, cfg),
                           jax.random.PRNGKey(0)))
        dp_spec = dp if len(dp) > 1 else dp[0]

        if compress:
            from repro.dist.collectives import ef_psum_tree

            def grads_local(params, batch, residual):
                loss, grads = _accumulate(local_loss, params, batch)
                loss = jax.lax.pmean(loss, dp)
                res_local = jax.tree.map(lambda r: r[0], residual)
                grads, new_res = ef_psum_tree(grads, res_local, dp, ndp)
                return loss, grads, jax.tree.map(lambda r: r[None], new_res)

            res_specs = jax.tree.map(lambda _: P(dp_spec), params_specs)
            grad_fn = jax.shard_map(
                grads_local, mesh=mesh,
                in_specs=(params_specs, ispecs_local, res_specs),
                out_specs=(P(), params_specs, res_specs),
                check_vma=False, axis_names=frozenset(dp))
        elif opts.zero2:
            # reduce-scatter each grad leaf along its ZeRO dim: fp32 grads
            # exist only as 1/ndp shards (memory) and the wire bytes halve
            # vs all-reduce (RS instead of RS+AG)
            pshapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                                     jax.random.PRNGKey(0))
            pspecs_real = shd.infer_param_specs(pshapes, mesh, cfg)
            flat_shapes, ptreedef = jax.tree.flatten(pshapes)
            flat_specs = ptreedef.flatten_up_to(pspecs_real)
            flat_zdims = [shd.zero_dim(s, sh.shape, mesh)
                          for s, sh in zip(flat_specs, flat_shapes)]

            def grads_local(params, batch):
                loss, grads = _accumulate(local_loss, params, batch)
                loss = jax.lax.pmean(loss, dp)
                flat_g = ptreedef.flatten_up_to(grads)
                out = []
                for g, d in zip(flat_g, flat_zdims):
                    if d is None:
                        out.append(jax.lax.pmean(g, dp))
                    else:
                        out.append(lax.psum_scatter(
                            g, dp, scatter_dimension=d, tiled=True) / ndp)
                return loss, jax.tree.unflatten(ptreedef, out)

            flat_gspecs = []
            for sh, d in zip(flat_shapes, flat_zdims):
                dims = [None] * len(sh.shape)
                if d is not None:
                    dims[d] = dp_spec
                flat_gspecs.append(P(*dims))
            gspecs = jax.tree.unflatten(ptreedef, flat_gspecs)
            grad_fn = jax.shard_map(
                grads_local, mesh=mesh,
                in_specs=(params_specs, ispecs_local),
                out_specs=(P(), gspecs),
                check_vma=False, axis_names=frozenset(dp))
        elif opts.abft_reduce != "off":
            from repro.dist.collectives import abft_psum_tree

            def grads_local(params, batch):
                loss, grads = _accumulate(local_loss, params, batch)
                loss = jax.lax.pmean(loss, dp)
                # ONE checksum-protected reduction (the paper's technique
                # applied to the grad collective, not just the matmuls)
                # single pair or a sequence of events — abft_psum_tree's
                # normalizer is the one place that distinction is resolved
                grads, ok = abft_psum_tree(
                    grads, dp, ndp, mode=opts.abft_reduce,
                    inject=opts.sdc_inject)
                return loss, grads, ok.astype(jnp.float32)

            grad_fn = jax.shard_map(
                grads_local, mesh=mesh,
                in_specs=(params_specs, ispecs_local),
                out_specs=(P(), params_specs, P()),
                check_vma=False, axis_names=frozenset(dp))
        else:
            def grads_local(params, batch):
                loss, grads = _accumulate(local_loss, params, batch)
                loss = jax.lax.pmean(loss, dp)
                # ONE reduction after accumulation (vs one per microbatch)
                grads = jax.lax.pmean(grads, dp)
                return loss, grads

            grad_fn = jax.shard_map(
                grads_local, mesh=mesh,
                in_specs=(params_specs, ispecs_local),
                out_specs=(P(), params_specs),
                check_vma=False, axis_names=frozenset(dp))
    else:
        grad_fn = functools.partial(_accumulate, loss_of)
    # the option validation above already rejects abft_reduce combined with
    # zero2 / compression / non-deferred reduction
    abft_reduce_on = opts.abft_reduce != "off"

    def step_fn(state, batch):
        params = state["params"]
        new_res = None
        reduce_ok = None
        extras = {}
        if "ef_residual" in state:
            loss, grads, new_res = grad_fn(params, batch, state["ef_residual"])
        elif abft_reduce_on:
            loss, grads, reduce_ok = grad_fn(params, batch)
        elif has_extras:
            loss, grads, extras = grad_fn(params, batch)
        else:
            loss, grads = grad_fn(params, batch)
        new_params, new_opt, metrics = adamw_update(
            grads, state["opt"], params, adamw)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if new_res is not None:
            new_state["ef_residual"] = new_res
        metrics = dict(metrics, loss=loss)
        if reduce_ok is not None:
            metrics["abft_ok"] = reduce_ok
        if "inv_ok" in extras:
            metrics["inv_ok"] = extras["inv_ok"].astype(jnp.float32)
        if "moe" in extras:
            metrics.update(zip(MOE_STATS, extras["moe"]))
        return new_state, metrics

    state_shapes = jax.eval_shape(
        functools.partial(init_state, cfg=cfg, opts=opts, mesh=mesh),
        jax.random.PRNGKey(0))
    sspecs = state_specs(state_shapes, mesh, opts, cfg)
    ispecs = _input_specs(cfg, shape, mesh)
    state_sh = shd.to_shardings(sspecs, mesh)
    in_shardings = (state_sh, shd.to_shardings(ispecs, mesh))
    # pin output state to the input shardings so the state round-trips
    # through the step without re-layout (required with donation)
    metric_sh = {"grad_norm": NamedSharding(mesh, P()),
                 "lr": NamedSharding(mesh, P()),
                 "loss": NamedSharding(mesh, P())}
    if abft_reduce_on:
        metric_sh["abft_ok"] = NamedSharding(mesh, P())
    if inv_on:
        metric_sh["inv_ok"] = NamedSharding(mesh, P())
    if moe_on:
        metric_sh.update({k: NamedSharding(mesh, P()) for k in MOE_STATS})
    out_shardings = (state_sh, metric_sh)
    return step_fn, in_shardings, out_shardings


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
                       opts: StepOptions = StepOptions()):
    cfg = _moe_cfg(cfg, mesh)

    def prefill_fn(params, batch, cache):
        logits, new_cache, _ = tf.forward(
            params, batch["tokens"], cfg, cache=cache,
            frames=batch.get("frames"), img_emb=batch.get("img_emb"),
            abft=opts.abft)
        return logits[:, -1], new_cache

    pshapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                             jax.random.PRNGKey(0))
    pspecs = shd.infer_param_specs(pshapes, mesh, cfg)
    if opts.fsdp:
        pspecs = jax.tree_util.tree_map_with_path(
            lambda path, sp: shd.zero1_spec(
                sp, _lookup(pshapes, path).shape, mesh), pspecs)
    cache_shapes = jax.eval_shape(
        lambda: tf.init_cache(cfg, shape.global_batch, shape.seq_len))
    cspecs = jax.tree_util.tree_map_with_path(
        shd.cache_specs(mesh, shape.global_batch, cfg), cache_shapes)
    ispecs = _input_specs(cfg, shape, mesh)
    cache_sh = shd.to_shardings(cspecs, mesh)
    in_sh = (shd.to_shardings(pspecs, mesh), shd.to_shardings(ispecs, mesh),
             cache_sh)
    out_sh = (NamedSharding(mesh, P(*shd.batch_specs(mesh, shape.global_batch),
                                    None)), cache_sh)
    return prefill_fn, in_sh, out_sh


def build_serve_step(cfg: ModelConfig, mesh: Mesh, shape: ShapeConfig,
                     opts: StepOptions = StepOptions()):
    """decode_* / long_* shapes: one new token against a seq_len KV cache."""
    cfg = _moe_cfg(cfg, mesh)

    def serve_fn(params, batch, cache):
        logits, new_cache = tf.decode_step(
            params, batch["tokens"], batch["pos"], cache, cfg,
            img_emb=batch.get("img_emb"), abft=opts.abft)
        return logits, new_cache

    pshapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                             jax.random.PRNGKey(0))
    pspecs = shd.infer_param_specs(pshapes, mesh, cfg)
    if opts.fsdp:
        pspecs = jax.tree_util.tree_map_with_path(
            lambda path, sp: shd.zero1_spec(
                sp, _lookup(pshapes, path).shape, mesh), pspecs)
    cache_shapes = jax.eval_shape(
        lambda: tf.init_cache(cfg, shape.global_batch, shape.seq_len))
    cspecs = jax.tree_util.tree_map_with_path(
        shd.cache_specs(mesh, shape.global_batch, cfg), cache_shapes)
    ispecs = _input_specs(cfg, shape, mesh)
    cache_sh = shd.to_shardings(cspecs, mesh)
    in_sh = (shd.to_shardings(pspecs, mesh), shd.to_shardings(ispecs, mesh),
             cache_sh)
    out_sh = (NamedSharding(mesh, P(*shd.batch_specs(mesh, shape.global_batch),
                                    None)), cache_sh)
    return serve_fn, in_sh, out_sh
