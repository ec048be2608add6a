"""Continuous-batching serving throughput with ABFT on/off, plus the
SDC-drill recovery accounting — the serving-side analogue of the paper's
Table 2 (FT overhead on a live workload) and §4.3 (fault-injection cost).

Warm-up discipline: each engine's compiled programs (prefill bucket +
decode_B) are warmed via `PagedServeEngine.warm()` with a SINGLE dummy
request (and the drill decode variant where one can fire), then the engine
is `reset()` and the real workload is timed — no real-request decode steps
are wasted on warming, and compile time never pollutes the timed rows.
"""
import time


def run():
    import jax
    import numpy as np
    from repro.configs.base import smoke_config
    from repro.ft.failures import SDCInjector, SDCPlan
    from repro.models import transformer as tf
    from repro.serve.engine import PagedServeEngine, Request

    lines = []
    cfg = smoke_config("qwen2-0.5b")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, 8).tolist() for _ in range(6)]
    n_new = 6

    def drive(engine):
        engine.warm(prompt_len=8)
        for i, p in enumerate(prompts):
            engine.submit(Request(rid=i, prompt=p, max_new_tokens=n_new))
        t0 = time.perf_counter()
        finished = engine.run()
        dt = time.perf_counter() - t0
        toks = sum(len(r.output) for r in finished)
        return dt / max(toks, 1), finished, engine.stats

    times = {}
    for mode in ("off", "verify"):
        us_tok, finished, s = drive(PagedServeEngine(
            cfg, params, slots=2, max_len=64, abft_mode=mode))
        times[mode] = us_tok
        lines.append((
            f"serving/qwen2-smoke/abft-{mode}", f"{us_tok*1e6:.0f}",
            f"tok_per_s={1/us_tok:.1f} requests={len(finished)} "
            f"prefill_ms={s.prefill_s*1e3:.1f} decode_ms={s.decode_s*1e3:.1f}"))
    lines.append(("serving/abft_overhead", f"{times['verify']*1e6:.0f}",
                  f"verify_vs_off={100*times['verify']/times['off']:.1f}%"))

    # --- protected decode-path reduction: clean overhead ----------------------
    us_clean, _, s_clean = drive(PagedServeEngine(
        cfg, params, slots=2, max_len=64, abft_reduce="correct"))
    assert s_clean.detections == 0, "clean protected run must see no faults"
    lines.append((
        "serving/qwen2-smoke/reduce-clean", f"{us_clean*1e6:.0f}",
        f"detections=0 reduce_vs_off={100*us_clean/times['off']:.1f}% "
        f"prefill_ms={s_clean.prefill_s*1e3:.1f} "
        f"decode_ms={s_clean.decode_s*1e3:.1f}"))

    # --- SDC drill: detection/correction + recovery latency -------------------
    sdc = SDCInjector(SDCPlan(((2, 0, 1e4), (7, 0, -3e4))))
    us_drill, fin_drill, s_drill = drive(PagedServeEngine(
        cfg, params, slots=2, max_len=64, abft_reduce="correct", sdc=sdc))
    assert s_drill.detections == len(s_drill.events) == 2
    assert s_drill.corrections == 2
    lines.append((
        "serving/qwen2-smoke/reduce-drill", f"{us_drill*1e6:.0f}",
        f"detections={s_drill.detections} corrections={s_drill.corrections} "
        f"drill_vs_clean={100*us_drill/us_clean:.1f}%"))
    lines.append((
        "serving/recovery_latency",
        f"{s_drill.recovery_latency_s()*1e6:.0f}",
        f"clean_step_us={s_clean.clean_step_mean_s()*1e6:.0f} "
        f"drilled_step_us={1e6*sum(s_drill.drilled_step_s)/max(len(s_drill.drilled_step_s),1):.0f}"))
    summ = s_drill.summary()
    lines.append((
        "serving/ttft", f"{summ['ttft_ms']*1e3:.0f}",
        f"tok_per_s={summ['tok_per_s']:.1f} requests={len(fin_drill)}"))

    # --- at-rest scrubber: KV/params verify-on-read cost ----------------------
    # scrub_every=1 is the worst case (every decode step re-verifies the
    # KV page checksums + the params scalar sums); production would
    # scrub every N steps, so the marginal per-step cost divides by N.
    us_scrub, _, s_scrub = drive(PagedServeEngine(
        cfg, params, slots=2, max_len=64, scrub_every=1))
    assert s_scrub.detections == 0, "clean scrubbed run must see no faults"
    assert s_scrub.scrub_checks > 0
    lines.append((
        "serving/qwen2-smoke/scrub-clean", f"{us_scrub*1e6:.0f}",
        f"scrub_checks={s_scrub.scrub_checks} scrub_repairs=0 "
        f"scrub_vs_off={100*us_scrub/times['off']:.1f}% "
        f"(worst case: scrub_every=1; amortizes as 1/N)"))
    return lines
