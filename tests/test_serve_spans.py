"""The serving engines' spans: one gather, decode and write-back span per
decode step, a scrub span on each scrub step, an admission span per
request on the FIFO and the scheduler path, and `EngineStats` timers that
are the spans' own durations."""
import jax
import pytest

from repro import obs
from repro.configs.base import smoke_config
from repro.models import transformer as tf
from repro.serve.engine import PagedServeEngine, Request
from repro.serve.scheduler import SchedPolicy, SLOScheduler

PROMPTS = (5, 20, 9, 30, 12)      # chunk 8: whole prefills and chunked ones
SCRUB_EVERY = 3


@pytest.fixture(scope="module")
def params():
    cfg = smoke_config("qwen2-0.5b")
    return cfg, tf.init_params(jax.random.PRNGKey(0), cfg)


def _run(params, scheduler):
    cfg, p = params
    eng = PagedServeEngine(cfg, p, slots=2, max_len=64, page_size=8,
                           chunk_prefill=8, prefix_cache=True,
                           scrub_every=SCRUB_EVERY, scheduler=scheduler)
    obs.reset_all()
    for i, n in enumerate(PROMPTS):
        eng.submit(Request(rid=i, prompt=[(3 * i + j) % 50 + 1
                                          for j in range(n)],
                           max_new_tokens=4))
    fin = eng.run()
    assert len(fin) == len(PROMPTS)
    evs = [e for e in obs.events() if e.name.startswith("serve/")]
    return eng, evs


@pytest.fixture(scope="module", params=["fifo", "scheduler"])
def run(request, params):
    sched = (SLOScheduler(SchedPolicy()) if request.param == "scheduler"
             else None)
    yield _run(params, sched)
    obs.reset_all()


def _named(evs, name):
    return [e for e in evs if e.name == name]


def test_one_gather_decode_writeback_emit_per_decode_step(run):
    eng, evs = run
    per_step = ("serve/kv_gather", "serve/decode_step",
                "serve/kv_writeback", "serve/emit")
    seq = [e.name for e in evs if e.name in per_step]
    assert seq == list(per_step) * eng.stats.decode_steps
    leaves = len(eng.kv.pools)
    for e in _named(evs, "serve/kv_writeback"):
        active = e.attrs["pages"] // leaves
        assert 1 <= active <= eng.slots
        assert e.attrs["pages"] == active * leaves
        assert e.attrs["host_reads"] == 1   # one read per write batch


def test_decode_step_span_counts_pages_live_and_read(run):
    """The decode reads every table entry; the live ones name a page."""
    eng, evs = run
    steps = _named(evs, "serve/decode_step")
    assert len(steps) == eng.stats.decode_steps
    for e in steps:
        assert e.attrs["pages_read"] == eng.slots * eng.kv.pages_per_slot
        assert 1 <= e.attrs["pages_live"] <= e.attrs["pages_read"]


def test_scrub_span_on_scrub_steps(run):
    eng, evs = run
    scrubs = _named(evs, "serve/scrub")
    assert len(scrubs) == eng.stats.scrub_checks >= 2
    assert [e.step % SCRUB_EVERY for e in scrubs] == [0] * len(scrubs)
    for child in ("serve/scrub_kv", "serve/scrub_params"):
        kids = _named(evs, child)
        assert len(kids) == len(scrubs)
        assert {e.parent for e in kids} == {"serve/scrub"}


def test_admit_span_carries_rid_and_queue_wait(run):
    eng, evs = run
    admits = _named(evs, "serve/admit")
    assert sorted(e.attrs["rid"] for e in admits) == list(range(len(PROMPTS)))
    assert all(e.attrs["queue_wait_ms"] >= 0.0 for e in admits)
    assert [e.attrs["prompt_len"] for e in sorted(
        admits, key=lambda e: e.attrs["rid"])] == list(PROMPTS)
    h = obs.histogram("repro_queue_wait_seconds").snapshot_one()
    assert h["count"] == len(PROMPTS)


def test_stats_timers_are_span_durations(run):
    eng, evs = run
    st = eng.stats
    assert st.decode_s == pytest.approx(
        sum(e.dur_s for e in _named(evs, "serve/decode_step")), abs=1e-9)
    assert st.decode_step_s == [e.dur_s for e in
                                _named(evs, "serve/decode_step")]
    prefills = [e for e in evs
                if e.name in ("serve/prefill", "serve/prefill_chunk")]
    assert {e.name for e in prefills} == {"serve/prefill",
                                         "serve/prefill_chunk"}
    assert st.prefill_s == pytest.approx(sum(e.dur_s for e in prefills),
                                         abs=1e-9)
    writes = _named(evs, "serve/kv_write")
    assert writes and {e.parent for e in writes} <= {
        "serve/prefill", "serve/prefill_chunk"}
    assert all(e.attrs["host_reads"] == 1 for e in writes)


def test_span_attrs_are_profiler_metadata(run):
    """Every attr of an engine span is a type a profiler annotation takes
    as metadata."""
    _, evs = run
    for e in evs:
        for k, v in e.attrs.items():
            assert isinstance(v, (str, int, float, bool)), (e.name, k, v)
