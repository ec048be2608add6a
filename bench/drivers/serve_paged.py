"""Open-loop serving through the program's `PagedServeEngine`.

Set-up makes the weights from the seed, builds the engine as the workload
file states, and warms every program the mix can need: one request for
each prefill and chunk shape in the support of the mix's prompt lengths.

The window submits each request when it is due (its ``t_submit`` is the
scheduled time, so queueing counts), steps the engine one step at a time
and stamps every new token.  TTFT is taken from the scheduled arrival; a
request with no first token when the window closes counts at
(close - due).  The inter-token gaps are all gaps between consecutive
tokens stamped in the window.  A window holds a couple of dozen requests
and a thousand or so gaps, so the TTFT reported is the median over every
request due and the inter-token time a 90th percentile: on a TPU v5e the
gaps fall in two groups, decode steps (about 0.2 s) and steps stalled
behind a prefill or a scrub (about 1 s), and a 95th percentile lands in
the sparse band between them and jumps by a fifth from run to run.

Correct: a sample of finished requests, drawn from the seed with the
longest among them, is run through the plain float32 reference over its
prompt and served tokens; the number compared is the widest gap by which a
served token's logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from bench import traffic
from bench.flops import DenseDecoder
from bench.harness import Check, span
from bench.program import model_config, program_params
from bench.reference import qwen2


def _pct(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def warm_prompt_lengths(mix: Dict, chunk: int) -> List[int]:
    """One prompt length per distinct program shape in the support of the
    mix: prompts up to ``chunk`` tokens prefill in one program whose
    host-side slices and scatters have the prompt's length; longer ones run
    chunks of ``chunk`` and then the remainder."""
    spec = mix["prompt"]
    lo, hi, scale = spec["lo"], spec["hi"], spec.get("scale", 1)
    support = sorted(set(range(lo, hi, scale)) | {hi})
    seen, out = set(), []
    for n in support:
        key = n if (not chunk or n <= chunk) else ("chunk", n % chunk or chunk)
        if key not in seen:
            seen.add(key)
            out.append(n)
    return out


class Driver:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.dims = DenseDecoder.from_config(cell.config)
        self.mix = cell.traffic
        self.engine_kw = dict(cell.workload["engine"])

    # -- set-up --------------------------------------------------------------
    def setup(self):
        from repro.serve.engine import PagedServeEngine, Request
        self._Request = Request
        conf = self.cell.config
        w = qwen2.init_weights(self.seed, self.dims, conf["torch_dtype"])
        self.engine = PagedServeEngine(model_config(conf), program_params(w),
                                       **self.engine_kw)
        del w
        lengths = warm_prompt_lengths(self.mix,
                                      self.engine_kw.get("chunk_prefill", 0))
        rng = np.random.default_rng(self.seed)
        slots = self.engine_kw["slots"]
        for start in range(0, len(lengths), slots):
            for i, n in enumerate(lengths[start:start + slots]):
                # 3 tokens: a decode step that does not retire its request
                # reads the slot's position, a program of its own
                self.engine.submit(Request(
                    rid=-1 - start - i,
                    prompt=rng.integers(0, self.dims.vocab, n).tolist(),
                    max_new_tokens=3))
            self.engine.run()
        self.engine.reset()

    # -- window --------------------------------------------------------------
    def window(self, seconds: float) -> Dict:
        eng, Request = self.engine, self._Request
        items = traffic.schedule(self.mix, self.seed, seconds, self.dims.vocab)
        reqs = [Request(rid=it.rid, prompt=list(it.prompt),
                        max_new_tokens=it.max_new) for it in items]
        seen = [0] * len(reqs)
        last = [0.0] * len(reqs)
        gaps: List[float] = []
        lateness: List[float] = []
        call_walls: List[float] = []
        outstanding: List[int] = []
        finished = set()
        # (seconds, requests without a first token, requests unfinished,
        #  tokens they still have to serve)
        backlog: List = []
        nxt = 0
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            while nxt < len(reqs) and t0 + items[nxt].due_s <= now:
                r = reqs[nxt]
                r.t_submit = t0 + items[nxt].due_s
                lateness.append(now - r.t_submit)
                with span("serve/submit"):
                    eng.submit(r)
                outstanding.append(nxt)
                nxt += 1
            if not outstanding:
                wake = t0 + items[nxt].due_s if nxt < len(reqs) else end
                with span("serve/idle_wait"):
                    time.sleep(max(0.0, min(wake, end) - now))
                continue
            c0 = time.perf_counter()
            with span("serve/engine_step"):
                done = eng.run(max_steps=1)
            t = time.perf_counter()
            call_walls.append(t - c0)
            for i in outstanding:
                r = reqs[i]
                n = len(r.output)
                for k in range(seen[i], n):
                    tk = r.t_first if k == 0 else t
                    if k > 0:
                        gaps.append(tk - last[i])
                    last[i] = tk
                seen[i] = n
            for r in done:
                finished.add(r.rid)
            outstanding = [i for i in outstanding
                           if reqs[i].rid not in finished]
            backlog.append((t - t0, sum(1 for i in outstanding
                                        if not reqs[i].output),
                            len(outstanding),
                            sum(reqs[i].max_new_tokens - len(reqs[i].output)
                                for i in outstanding)))
        t_close = time.perf_counter()
        ttft = []
        for i in range(nxt):
            r = reqs[i]
            ttft.append((r.t_first - r.t_submit) if r.t_first
                        else (t_close - r.t_submit))
        # requests never submitted (the loop overran the schedule) are late
        ttft += [t_close - (t0 + it.due_s) for it in items[nxt:]
                 if t0 + it.due_s <= t_close]
        s = eng.stats
        return {
            "attempted": len(items), "failed": len(eng.rejected),
            "submitted": nxt, "finished": len(finished),
            "window_s": t_close - t0, "ttft_s": ttft, "itl_s": gaps,
            "lateness_s": lateness, "call_walls_s": call_walls,
            "backlog": backlog,
            "decode_step_s": list(s.decode_step_s),
            "decode_steps": s.decode_steps, "prefills": s.prefills,
            "prefill_s": s.prefill_s,
            "prefill_tokens": self._prefill_positions(reqs, nxt),
            "detections": s.detections, "scrub_checks": s.scrub_checks,
            "prefix_hits": eng.kv.stats.prefix_hits,
            "prefix_misses": eng.kv.stats.prefix_misses,
            "finished_reqs": [reqs[i] for i in range(nxt)
                              if reqs[i].rid in finished],
        }

    @staticmethod
    def _prefill_positions(reqs, n) -> List[List[int]]:
        """[start, end) of the prompt positions each admitted request
        prefilled in the window (all of its prompt: the engine's prefix
        cache reports its hits apart)."""
        return [[0, len(r.prompt)] for r in reqs[:n] if r.t_first]

    def end_to_end(self, st: Dict) -> Dict[str, float]:
        return {"ttft_p50_ms": 1e3 * _pct(st["ttft_s"], 50),
                "itl_p90_ms": 1e3 * _pct(st["itl_s"], 90)}

    def report_lines(self, st: Dict) -> List[str]:
        lat = st["lateness_s"]
        return [
            f"bench: requests due {st['attempted']} submitted "
            f"{st['submitted']} finished {st['finished']} rejected "
            f"{st['failed']}; decode steps {st['decode_steps']}, prefills "
            f"{st['prefills']}, scrubs {st['scrub_checks']}, prefix hits "
            f"{st['prefix_hits']} misses {st['prefix_misses']}",
            f"bench: generator lateness p50 {1e3 * _pct(lat, 50):.3f} ms "
            f"max {1e3 * max(lat, default=0.0):.3f} ms over {len(lat)} "
            f"submissions",
            "bench: ttft ms " + " ".join(
                f"p{q} {1e3 * _pct(st['ttft_s'], q)!r}"
                for q in (50, 90, 95, 99)) + f" over {len(st['ttft_s'])}; "
            "itl ms " + " ".join(
                f"p{q} {1e3 * _pct(st['itl_s'], q)!r}"
                for q in (50, 90, 95, 98, 99)) + f" over "
            f"{len(st['itl_s'])} gaps",
            f"bench: engine steps {len(st['call_walls_s'])} taking "
            f"{sum(st['call_walls_s']):.3f} s: decode "
            f"{sum(st['decode_step_s']):.3f} s, prefill "
            f"{st['prefill_s']:.3f} s",
        ]

    # -- after the window ----------------------------------------------------
    def release(self):
        self.engine = None
        gc.collect()

    def sample(self, st: Dict) -> List:
        """Finished requests to check: the longest, then others drawn from
        the seed until the sample holds ``check_tokens`` served tokens."""
        fin = sorted(st["finished_reqs"],
                     key=lambda r: (-len(r.output), r.rid))
        if not fin:
            return []
        want = int(self.cell.workload["check_tokens"])
        rng = np.random.default_rng(self.seed + 1)
        picked = [fin[0]]
        for j in rng.permutation(len(fin) - 1) + 1:
            if sum(len(r.output) for r in picked) >= want:
                break
            picked.append(fin[int(j)])
        return picked

    def check(self, st: Dict) -> List[Check]:
        import jax
        import jax.numpy as jnp
        conf = self.cell.config
        picked = self.sample(st)
        lim = self.cell.limits["logit_gap"]
        if not picked:
            return [Check("logit_gap", float("inf"), lim)]
        w = qwen2.init_weights(self.seed, self.dims, conf["torch_dtype"])
        s_max = self.mix["prompt"]["hi"] + self.mix["output"]["hi"]
        o_max = self.mix["output"]["hi"]
        fn = jax.jit(lambda w, t, i: qwen2.logits_at(
            w, t, i, self.dims, float(conf["rope_theta"]),
            float(conf["rms_norm_eps"])))
        worst, n_tok = 0.0, 0
        for r in picked:
            seq = list(r.prompt) + list(r.output[:-1])
            toks = np.zeros(s_max, np.int32)
            toks[:len(seq)] = seq
            idx = np.zeros(o_max, np.int32)
            n = len(r.output)
            idx[:n] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + n)
            z = np.asarray(fn(w, jnp.asarray(toks), jnp.asarray(idx)))[:n]
            served = z[np.arange(n), np.asarray(r.output)]
            worst = max(worst, float(np.max(z.max(axis=-1) - served)))
            n_tok += n
        print(f"bench: checked {len(picked)} requests, {n_tok} served tokens",
              flush=True)
        return [Check("logit_gap", worst, lim)]


def control(driver):
    """The program's own path one precision below the configuration's
    bfloat16: protected projections on dynamically quantized int8
    operands."""
    driver.engine_kw["kernel_dtype"] = "int8"


def _token_altered(driver):
    """Every request's first token is replaced where the engine produces it."""
    setup = driver.setup

    def altered_setup():
        setup()
        eng, vocab = driver.engine, driver.dims.vocab
        finish = eng._finish_admission
        eng._finish_admission = lambda s, req, tok, plen: finish(
            s, req, (tok + 1) % vocab, plen)
    driver.setup = altered_setup


FAULTS = {"token_altered": _token_altered}
