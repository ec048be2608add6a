"""Training through the program's jitted step (`train.step.build_train_step`).

Set-up builds one object, the compiled step with its state, as the
program's training launcher does (one-device ("data", "model") mesh,
donated state), and drives it from the seed through its first three steps
(the first of which compiles).  Those steps are the ones checked: their
losses, the first gradient as the optimizer holds it after step 1 (its
first moment over 1 - beta1), and each leaf's change after step 3.  The
window then runs the same object on, step after step, on fresh batches of
a seeded Zipf token stream, one step in flight.

Correct: after the window the program's state is freed and the plain
float32 reference follows the same three steps on the same batches, row by
row at the highest matmul precision.  Each number is a worst leaf: the gap
between the program's norm and the reference's, over the larger of the
reference's norm of that leaf and of the median leaf.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Dict, List

import numpy as np

from bench import traffic
from bench.flops import DenseDecoder
from bench.harness import Check, span
from bench.program import bench_leaves, model_config, program_params
from bench.reference import adamw as ref_adamw
from bench.reference import qwen2

CHECKED_STEPS = 3


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)
    return {k: float(v) for k, v in norms.items()}


def _diff_norms(a, b) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda x, y: {k: jnp.sqrt(jnp.sum(jnp.square(
        x[k].astype(jnp.float32) - y[k].astype(jnp.float32))))
        for k in x})(a, b)
    return {k: float(v) for k, v in norms.items()}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keys) -> Dict[str, float]:
    """Per leaf, the gap between the program's norm and the reference's
    over the larger of the reference's norm of that leaf and of the median
    leaf."""
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in keys}


def _worst(gaps: Dict[str, float]) -> str:
    k = max(gaps, key=gaps.get)
    return f"{k} {gaps[k]!r}"


class Driver:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.dims = DenseDecoder.from_config(cell.config)
        self.mix = cell.traffic
        self.batch, self.seq = int(self.mix["batch"]), int(self.mix["seq_len"])
        self.hp = dict(cell.workload["adamw"])
        self._ctx = contextlib.ExitStack()

    def _batch(self, step: int):
        return traffic.batch_tokens(self.seed, step, self.batch, self.seq,
                                    self.dims.vocab, self.mix["zipf_a"])

    def _feed(self, step: int):
        import jax
        toks, labels = self._batch(step)
        return jax.device_put({"tokens": toks, "labels": labels},
                              self._in_sh[1])

    def _call(self, state, batch):
        return self._step(state, batch)

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
        params = program_params(qwen2.init_weights(self.seed, self.dims,
                                                   conf["torch_dtype"]))
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
        w0 = qwen2.init_weights(self.seed, self.dims, conf["torch_dtype"])
        self.change = _diff_norms(bench_leaves(state["params"]), w0)
        del w0
        self.state = state
        self.next_step = CHECKED_STEPS

    # -- window --------------------------------------------------------------
    def window(self, seconds: float) -> Dict:
        state, step = self.state, self.next_step
        steps, losses, pending = 0, [], None
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            with span("train/feed"):
                batch = self._feed(step)
            with span("train/dispatch"):
                state, metrics = self._call(state, batch)
            if pending is not None:
                with span("train/wait"):
                    losses.append(float(pending["loss"]))
            pending = metrics
            steps += 1
            step += 1
            if time.perf_counter() >= end:
                break
        with span("train/wait"):
            losses.append(float(pending["loss"]))
        t_close = time.perf_counter()
        self.state, self.next_step = state, step
        return {"attempted": steps,
                "failed": sum(1 for x in losses if not np.isfinite(x)),
                "steps": steps, "window_s": t_close - t0,
                "tokens": steps * self.batch * self.seq,
                "batch": self.batch, "seq": self.seq, "losses": losses}

    def end_to_end(self, st: Dict) -> Dict[str, float]:
        return {"train_tokens_per_s": st["tokens"] / st["window_s"]}

    def report_lines(self, st: Dict) -> List[str]:
        return [f"bench: {st['steps']} steps of {st['batch']} x {st['seq']} "
                f"tokens in {st['window_s']:.3f} s; checked losses "
                f"{self.losses}; window losses {st['losses'][0]!r} .. "
                f"{st['losses'][-1]!r}"]

    # -- after the window ----------------------------------------------------
    def release(self):
        self.state = None
        self._step = None
        self._ctx.close()
        gc.collect()

    def reference(self):
        """The reference's losses, first clipped gradient and change over
        the checked steps, per benchmark leaf."""
        import jax
        import jax.numpy as jnp
        conf = self.cell.config
        theta, eps = float(conf["rope_theta"]), float(conf["rms_norm_eps"])
        store = jnp.dtype(conf["torch_dtype"])
        dims = self.dims

        @jax.jit
        def grad_rows(w, toks, labels):
            def row(carry, tl):
                lsum, gsum = carry
                l, g = jax.value_and_grad(qwen2.loss)(
                    w, tl[0], tl[1], dims, theta, eps)
                return (lsum + l, jax.tree.map(jnp.add, gsum, g)), None
            g0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
            (lsum, gsum), _ = jax.lax.scan(row, (jnp.zeros(()), g0),
                                           (toks, labels))
            n = toks.shape[0]
            return lsum / n, jax.tree.map(lambda g: g / n, gsum)

        w = qwen2.init_weights(self.seed, dims, conf["torch_dtype"])
        opt = ref_adamw.init(w)
        losses, grad1 = [], None
        for i in range(CHECKED_STEPS):
            toks, labels = self._batch(i)
            loss, g = grad_rows(w, jnp.asarray(toks), jnp.asarray(labels))
            losses.append(float(loss))
            w, opt, scale = ref_adamw.update(w, g, opt, i + 1, self.hp, store)
            if i == 0:
                s = float(scale)
                grad1 = {k: v * s for k, v in _leaf_norms(g).items()}
            del g
        del opt
        # the initial weights again, from the seed, once the gradients and
        # moments are gone
        change = _diff_norms(w, qwen2.init_weights(self.seed, dims,
                                                   conf["torch_dtype"]))
        return losses, grad1, change

    def check(self, st: Dict) -> List[Check]:
        lim = self.cell.limits
        r_losses, r_grad1, r_change = self.reference()
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(self.losses,
                                                            r_losses))
        g_gaps = leaf_gaps(self.grad1, r_grad1, r_grad1)
        med = float(np.median(list(r_grad1.values())))
        # leaves whose reference gradient is nought to rounding (a key's
        # bias under softmax) move under Adam by round-off alone
        moving = [k for k, v in r_grad1.items() if v >= 1e-3 * med]
        c_gaps = leaf_gaps(self.change, r_change, moving)
        print(f"bench: reference losses {r_losses}; worst leaves: first "
              f"gradient {_worst(g_gaps)}, change {_worst(c_gaps)}; left "
              f"out of the change: {sorted(set(r_grad1) - set(moving))}",
              flush=True)
        return [Check("loss_gap", loss_gap, lim["loss_gap"]),
                Check("grad1_gap", max(g_gaps.values()), lim["grad1_gap"]),
                Check("change_gap", max(c_gaps.values()),
                      lim["change_gap"])]


def control(driver):
    """The program's own path one precision below the configuration's
    bfloat16: protected projections on dynamically quantized int8
    operands."""
    driver.cell.workload["step"]["kernel_dtype"] = "int8"


def _half_batch(driver):
    """The program sees the first half of each batch twice: the mean is
    taken over half the rows (the reference keeps the whole batch)."""
    def feed(step):
        import jax
        toks, labels = driver._batch(step)
        h = toks.shape[0] // 2
        toks[h:2 * h], labels[h:2 * h] = toks[:h], labels[:h]
        return jax.device_put({"tokens": toks, "labels": labels},
                              driver._in_sh[1])
    driver._feed = feed


def _frozen_state(driver):
    """The step returns the state it was given."""
    call = driver._call

    def frozen(state, batch):
        import jax
        import jax.numpy as jnp
        keep = jax.tree.map(jnp.copy, state)
        _, metrics = call(state, batch)
        return keep, metrics
    driver._call = frozen


FAULTS = {"half_batch": _half_batch, "frozen_state": _frozen_state}
