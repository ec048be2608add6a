"""Golden decode-parity for the heavy-traffic serving layer.

The load-bearing guarantee: `PagedServeEngine` (paged KV + chunked
prefill + prefix cache + SLO scheduler) emits, for every request of a
trace, the token stream of single-request greedy decoding over a dense
contiguous cache (the `greedy_reference` fixture) — both CLEAN and
DRILLED (mid-decode SDCs corrected by the abft residual, page-granular
DRAM corruption erasure-repaired by the per-page checksums).  Plus trace
determinism and `compare()` accounting.

Fault schedules index EXECUTED decode steps recorded from the clean
paged replay (run_trace fast-forwards the decode-step clock over idle
gaps, so raw step numbers can be skipped); the drilled replay is
step-identical because every fault is corrected.
"""
import dataclasses

import pytest

from repro.serve.traffic import (TrafficConfig, TrafficReport, compare,
                                 make_trace, run_trace)

PAGE = 8


@pytest.fixture(scope="module")
def setup():
    import jax
    from repro.configs.base import smoke_config
    from repro.models import transformer as tf

    cfg = smoke_config("qwen2-0.5b")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def paged(setup, sdc=None, **kw):
    from repro.serve.engine import PagedServeEngine
    from repro.serve.scheduler import SchedPolicy, SLOScheduler

    cfg, params = setup
    kw.setdefault("chunk_prefill", 2 * PAGE)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("scheduler", SLOScheduler(SchedPolicy(max_queue=64)))
    e = PagedServeEngine(cfg, params, slots=3, max_len=64, page_size=PAGE,
                         scrub_every=1, abft_reduce="correct", sdc=sdc, **kw)
    e.warm(prompt_len=8, decode_steps=2)
    e.reset()
    return e


def contiguous(setup, greedy_reference, trace):
    """Each request's greedy token stream over a dense contiguous cache,
    keyed by rid."""
    cfg, params = setup
    return {it.rid: greedy_reference(cfg, params, list(it.prompt),
                                     it.max_new) for it in trace}


def trace_cfg(**kw):
    kw.setdefault("n_requests", 8)
    kw.setdefault("vocab", 512)
    kw.setdefault("arrival", "open")
    kw.setdefault("rate_per_step", 0.7)
    kw.setdefault("prompt_max", 24)
    kw.setdefault("out_max", 6)
    kw.setdefault("shared_prefix_len", 2 * PAGE)
    kw.setdefault("seed", 5)
    return TrafficConfig(**kw)


# ---------------------------------------------------------------------------
# trace determinism + report plumbing
# ---------------------------------------------------------------------------


def test_trace_is_deterministic():
    a, b = make_trace(trace_cfg()), make_trace(trace_cfg())
    assert a == b
    c = make_trace(trace_cfg(seed=6))
    assert c != a
    shared = a[0].prompt[:2 * PAGE]
    assert all(it.prompt[:2 * PAGE] == shared for it in a), \
        "shared system prompt must be a literal shared prefix"


def test_open_arrivals_monotone_and_zipf_bounded():
    cfg = trace_cfg(n_requests=32, prompt_min=4)
    tr = make_trace(cfg)
    arr = [it.arrive_step for it in tr]
    assert arr == sorted(arr) and arr[-1] > 0
    for it in tr:
        assert cfg.prompt_min <= len(it.prompt) <= cfg.prompt_max
        assert cfg.out_min <= it.max_new <= cfg.out_max


def test_compare_accounting():
    base = dict(n_requests=2, n_finished=2, n_rejected=0, wall_s=1.0,
                decode_steps=10, total_tokens=20, tok_per_s=20.0,
                p50_ttft_ms=10.0, p99_ttft_ms=20.0, mean_ttft_ms=12.0,
                detections=0, corrections=0, sdc_events=0, sdc_corrected=0,
                scrub_checks=5, scrub_repairs=0, prefix_hits=0,
                outputs={0: [1, 2], 1: [3]})
    clean = TrafficReport(**base)
    fault = TrafficReport(**{**base, "p99_ttft_ms": 30.0, "tok_per_s": 16.0,
                             "detections": 3, "corrections": 3})
    d = compare(clean, fault, expected_faults=3)
    assert d["p99_ttft_degradation_pct"] == pytest.approx(50.0)
    # throughput degradation is a slowdown ratio: clean/fault - 1
    assert d["tok_per_s_degradation_pct"] == pytest.approx(25.0)
    assert d["faults_injected"] == 3 and d["faults_missed"] == 0
    assert d["token_streams_identical"]
    bad = TrafficReport(**{**base, "outputs": {0: [1, 9], 1: [3]},
                           "detections": 1})
    d2 = compare(clean, bad, expected_faults=3)
    assert d2["faults_missed"] == 2
    assert not d2["token_streams_identical"]


# ---------------------------------------------------------------------------
# golden parity: paged == greedy over a contiguous cache, clean and drilled
# ---------------------------------------------------------------------------


def test_paged_matches_contiguous_clean(setup, greedy_reference):
    tr = make_trace(trace_cfg())
    ref = contiguous(setup, greedy_reference, tr)
    got = run_trace(paged(setup), tr)
    assert got.n_finished == len(tr)
    assert got.outputs == ref, \
        "paged engine must be bit-identical to contiguous greedy decode"
    assert got.prefix_hits > 0, "shared 2-page prefix should hit the cache"


def test_unchunked_paged_matches_chunked(setup):
    tr = make_trace(trace_cfg(seed=11))
    a = run_trace(paged(setup), tr)
    b = run_trace(paged(setup, chunk_prefill=0, prefix_cache=False), tr)
    assert a.outputs == b.outputs


def test_paged_matches_contiguous_drilled(setup, greedy_reference):
    """The same golden trace under live faults: two mid-decode SDCs on the
    logits reduction and two page-granular DRAM flips, all corrected
    in-flight — the token streams still match greedy decoding over a
    contiguous cache."""
    from repro.ft.failures import SDCInjector, SDCPlan

    tr = make_trace(trace_cfg(seed=7))
    ref = contiguous(setup, greedy_reference, tr)

    seen = []
    clean = run_trace(paged(setup), tr,
                      on_step=lambda e, s: seen.append(s))
    assert clean.outputs == ref
    sdc_steps = (seen[len(seen) // 3], seen[len(seen) // 2])
    dram_steps = {seen[2 * len(seen) // 3], seen[(5 * len(seen)) // 6]}

    eng = paged(setup, sdc=SDCInjector(
        SDCPlan(tuple((s, 0, 1e4) for s in sdc_steps))))
    fired = []

    def drill(e, step):
        if step in dram_steps and step not in fired:
            fired.append(step)
            key = next(iter(e.kv.pools))
            live = e.kv.live_pages()
            e.kv.corrupt_page(key, live[len(fired) % len(live)], bit=30)

    fault = run_trace(eng, tr, on_step=drill)
    assert len(fired) == len(dram_steps), "dram faults did not fire"
    assert fault.outputs == ref, \
        "drilled paged engine must still match contiguous greedy decode"
    assert fault.sdc_events == len(sdc_steps) == fault.sdc_corrected
    assert fault.scrub_repairs >= len(dram_steps)
    d = compare(clean, fault,
                expected_faults=len(sdc_steps) + len(dram_steps))
    assert d["faults_missed"] == 0
    assert d["token_streams_identical"]
    eng.kv.check_invariants()  # raises on violation
    assert eng.kv.checksums_consistent()


def test_rejection_under_tiny_queue(setup):
    """Admission control surfaces as rejected requests, not hangs."""
    from repro.serve.scheduler import SchedPolicy, SLOScheduler

    eng = paged(setup, scheduler=SLOScheduler(SchedPolicy(max_queue=1)))
    tr = make_trace(trace_cfg(arrival="closed", n_requests=8))
    rep = run_trace(eng, tr)
    assert rep.n_rejected > 0
    assert rep.n_finished + rep.n_rejected == len(tr)
    assert rep.n_finished >= 1
