"""Training a MoE decoder on one chip's share of the experts through the
program's jitted step (`train.step.build_train_step`).

The driver of `train_step` (the dense cells') with the model, weights,
reference and FLOP count of `bench.program_moe`,
`bench.reference.qwen3_moe` and `bench.flops_moe`: the same set-up (three
checked steps, the first of which compiles), window and comparison.  The
step also returns the held-expert layer's counters (`repro.models.moe.
MOE_STATS`); the driver keeps them for every step it runs and reads them
after the window.

Correct: as `train_step` (the program's losses, first gradient and
change after three steps against the plain float32 reference on the same
batches, worst leaf), plus ``unrouted_rows``: over every step, the
assignments to held experts that did not get a row of the grouped
product's buffer.  The layer is dropless by construction; this counts it.
"""
from __future__ import annotations

from typing import Dict, List

from bench.drivers.train_step import (CHECKED_STEPS, FAULTS, Driver as _Dense,
                                      _diff_norms, _leaf_norms, control)
from bench.flops_moe import MoEDecoder
from bench.harness import Check
from bench.program_moe import bench_leaves, model_config, program_params
from bench.reference import adamw as ref_adamw
from bench.reference import qwen3_moe

__all__ = ["Driver", "control", "FAULTS"]


class Driver(_Dense):
    def __init__(self, cell, seed: int, devices):
        from repro.models.moe import MOE_STATS
        super().__init__(cell, seed, devices)
        self.dims = MoEDecoder.from_config(cell.config)
        self.stat_names = MOE_STATS
        self._moe: List[Dict] = []

    def _call(self, state, batch):
        state, metrics = self._step(state, batch)
        self._moe.append({k: metrics[k] for k in self.stat_names})
        return state, metrics

    def _moe_totals(self, since: int = 0) -> Dict[str, float]:
        rows = [{k: float(v) for k, v in m.items()}
                for m in self._moe[since:]]
        out = {k: sum(r[k] for r in rows) for k in self.stat_names}
        out["moe_max_group"] = max((r["moe_max_group"] for r in rows),
                                   default=0.0)
        return out

    # -- set-up --------------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ShapeConfig
        from repro.dist.sharding import make_mesh
        from repro.train.optimizer import AdamWConfig, adamw_init
        from repro.train.step import StepOptions, build_train_step
        conf = self.cell.config
        cfg = model_config(conf)
        mesh = make_mesh((1, 1), ("data", "model"), devices=self.devices[:1])
        self._ctx.enter_context(jax.set_mesh(mesh))
        shape = ShapeConfig("bench", self.seq, self.batch, "train")
        opts = StepOptions(**self.cell.workload["step"])
        step_fn, in_sh, out_sh = build_train_step(
            cfg, mesh, shape, AdamWConfig(**self.hp), opts)
        self._in_sh = in_sh
        self._step = jax.jit(step_fn, in_shardings=in_sh,
                             out_shardings=out_sh, donate_argnums=(0,))
        params = program_params(qwen3_moe.init_weights(
            self.seed, self.dims, conf["torch_dtype"]))
        state = {"params": params, "opt": adamw_init(params),
                 "step": jnp.zeros((), jnp.int32)}
        state = jax.device_put(state, in_sh[0])
        del params
        self.losses, self.grad1 = [], None
        for i in range(CHECKED_STEPS):
            state, metrics = self._call(state, self._feed(i))
            self.losses.append(float(metrics["loss"]))
            if i == 0:
                b1 = float(self.hp["b1"])
                self.grad1 = {k: v / (1.0 - b1) for k, v in _leaf_norms(
                    bench_leaves(state["opt"]["m"])).items()}
        w0 = qwen3_moe.init_weights(self.seed, self.dims, conf["torch_dtype"])
        self.change = _diff_norms(bench_leaves(state["params"]), w0)
        del w0
        self.state = state
        self.next_step = CHECKED_STEPS

    # -- window --------------------------------------------------------------
    def window(self, seconds: float) -> Dict:
        start = len(self._moe)
        st = super().window(seconds)
        st.update(self._moe_totals(start))
        return st

    def report_lines(self, st: Dict) -> List[str]:
        return super().report_lines(st) + [
            f"bench: window moe assignments {st['moe_assignments']!r}, rows "
            f"computed {st['moe_rows_computed']!r}, largest expert "
            f"{st['moe_max_group']!r}, verify trips {st['moe_trips']!r}"]

    # -- after the window ----------------------------------------------------
    def reference(self):
        """The reference's losses, first clipped gradient and change over
        the checked steps, per benchmark leaf."""
        import jax
        import jax.numpy as jnp
        conf = self.cell.config
        theta, eps = float(conf["rope_theta"]), float(conf["rms_norm_eps"])
        coef = float(self.cell.workload["step"]["aux_weight"])
        store = jnp.dtype(conf["torch_dtype"])
        dims = self.dims
        grad = jax.jit(jax.value_and_grad(
            lambda w, t, l: qwen3_moe.loss(w, t, l, dims, theta, eps, coef)))
        w = qwen3_moe.init_weights(self.seed, dims, conf["torch_dtype"])
        opt = ref_adamw.init(w)
        losses, grad1 = [], None
        for i in range(CHECKED_STEPS):
            toks, labels = self._batch(i)
            loss, g = grad(w, jnp.asarray(toks), jnp.asarray(labels))
            losses.append(float(loss))
            w, opt, scale = ref_adamw.update(w, g, opt, i + 1, self.hp, store)
            if i == 0:
                s = float(scale)
                grad1 = {k: v * s for k, v in _leaf_norms(g).items()}
            del g
        del opt
        change = _diff_norms(w, qwen3_moe.init_weights(
            self.seed, dims, conf["torch_dtype"]))
        return losses, grad1, change

    def check(self, st: Dict) -> List[Check]:
        tot = self._moe_totals()
        unrouted = tot["moe_assignments"] - tot["moe_rows_routed"]
        print(f"bench: {len(self._moe)} steps routed "
              f"{tot['moe_assignments']!r} assignments to held experts, "
              f"{tot['moe_rows_routed']!r} rows; verify trips "
              f"{tot['moe_trips']!r}", flush=True)
        return super().check(st) + [
            Check("unrouted_rows", unrouted,
                  self.cell.limits["unrouted_rows"])]
