"""The readers of the program's own spans: on a traced CPU run of the tiny
chat cell, on a trace written out by hand whose host events carry
metadata, and on a program that has no such spans.  Also the names the
engine gives its compiled programs, which `prefill_mfu` reads."""
import math
from pathlib import Path

import pytest

from bench import harness, program_spans, trace
from bench.metrics_context import Context
from bench.tests import util

METRICS = Path(__file__).resolve().parents[1] / "metrics"
READERS = ("queue_wait_p50_ms", "kv_writeback_ms_per_step",
           "kv_gather_ms_per_step", "scrub_ms_per_step",
           "prefill_kv_write_ms_per_request")


def _reader(name):
    return harness.load_module(METRICS / f"{name}.py",
                               "test_metric_" + name).read


def _event(meta, s, e, stats=()):
    st = "".join(f" stats {{ metadata_id: {k} {kind}_value: {v} }}"
                 for k, kind, v in stats)
    return (f"events {{ metadata_id: {meta} offset_ps: {s * 10**6} "
            f"duration_ps: {(e - s) * 10**6}{st} }}")


# host spans in microseconds: the window, two engine steps, the program's
# spans inside them with their metadata (rid 10, queue_wait_ms 20)
HOST = [(1, 0, 100), (2, 0, 40), (3, 5, 15, ((10, "int64", 7),
                                            (11, "double", 2.5))),
        (4, 20, 30), (2, 50, 90), (4, 60, 75)]
NAMES = {1: "bench/window", 2: "serve/engine_step", 3: "serve/admit",
         4: "serve/kv_writeback"}


def _summary(rows=HOST):
    from jax.profiler import ProfileData
    events = " ".join(_event(m, s + 1000, e + 1000, *rest)
                      for m, s, e, *rest in rows)
    metas = "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: '
                    f'"{n}" }} }}' for i, n in NAMES.items())
    stats = ' stat_metadata { key: 10 value { id: 10 name: "rid" } }' \
            ' stat_metadata { key: 11 value { id: 11 name: "queue_wait_ms" } }'
    text = (f'planes {{ id: 1 name: "/host:CPU" lines {{ id: 1 name: '
            f'"python" timestamp_ns: 0 {events} }}{metas}{stats} }}')
    return trace.reduce(ProfileData.from_text_proto(text))


@pytest.fixture
def bus():
    from repro import obs
    obs.reset_all()
    yield obs
    obs.reset_all()


def test_annotated_host_events_reduce_to_plain_spans(bus):
    s = _summary()
    names = [n for _, _, n in s.spans]
    assert names.count("serve/admit") == 1
    assert names.count("serve/kv_writeback") == 2
    assert program_spans.total_s(s, "serve/kv_writeback") == \
        pytest.approx(25e-6)
    # the window's spans are the last ones of each name on the bus
    with bus.span("serve/admit", rid=1, queue_wait_ms=0.0):
        pass                                    # before the window
    with bus.span("serve/admit", rid=7, queue_wait_ms=2.5):
        pass
    assert program_spans.attrs(s, "serve/admit") == \
        [{"rid": 7, "queue_wait_ms": 2.5}]
    # a bus that holds fewer such spans than the trace gives nothing
    assert program_spans.attrs(s, "serve/kv_writeback") is None


def test_readers_on_hand_written_trace(bus):
    with bus.span("serve/admit", rid=7, queue_wait_ms=2.5):
        pass
    for _ in range(2):
        with bus.span("serve/kv_writeback", pages=4, host_reads=8):
            pass
    ctx = Context(cell=None, stats={"decode_steps": 2, "prefills": 1,
                                    "ttft_s": [1.0, 2.0]},
                  trace=_summary(), peaks={})
    got = {}
    for name in READERS:
        ctx.metric = name
        got[name] = _reader(name)(ctx)
    assert got == {"queue_wait_p50_ms": 2.5,
                   "kv_writeback_ms_per_step": pytest.approx(12.5e-3),
                   "kv_gather_ms_per_step": None, "scrub_ms_per_step": None,
                   "prefill_kv_write_ms_per_request": None}
    assert any("pages 4.0, host_reads 8.0 per decode_step" in n
               for n in ctx.notes)
    assert any("1 of the 2 requests due never admitted" in n
               for n in ctx.notes)


def test_readers_find_nothing_without_program_spans(bus):
    """A program that opens no such spans (the benchmark's traced runs
    also run on one): every reader returns None and none raises."""
    rows = [r for r in HOST if r[0] in (1, 2)]
    ctx = Context(cell=None, stats={"decode_steps": 2, "prefills": 1,
                                    "ttft_s": [1.0]},
                  trace=_summary(rows), peaks={})
    for name in READERS:
        ctx.metric = name
        assert _reader(name)(ctx) is None
    assert ctx.notes == []


def test_traced_chat_run_reads_program_spans(tmp_path, monkeypatch):
    kept = []
    reduce_dir = trace.reduce_dir

    def keep(*a, **kw):
        kept.append(reduce_dir(*a, **kw))
        return kept[-1]
    monkeypatch.setattr(trace, "reduce_dir", keep)
    bench = util.tiny_bench(tmp_path)
    r = util.run_cell(bench, "tiny.chat", seed=2**33 + 7, seconds=3.0,
                      trace=True)
    assert r["correct"], r["checks"]
    for name in READERS:
        v = r["metrics"][name]["value"]
        assert math.isfinite(v) and v >= 0.0, (name, v)
    (s,) = kept
    names = {n for _, _, n in s.spans}
    assert set(program_spans.ENGINE) - {"serve/prefill"} <= names
    assert {"serve/kv_write", "serve/scrub_kv",
            "serve/scrub_params"} <= names


def test_program_names_and_prefill_mfu_regex():
    """The engine's compiled programs carry stable names, and
    `prefill_mfu` counts both prefill programs and never a decode."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import smoke_config
    from repro.models import transformer as tf
    from repro.serve.engine import PagedServeEngine

    cfg = smoke_config("qwen2-0.5b")
    eng = PagedServeEngine(cfg, tf.init_params(jax.random.PRNGKey(0), cfg),
                           slots=2, max_len=32, page_size=8,
                           chunk_prefill=8, abft_reduce="correct")
    p, i32 = eng.params, jnp.int32
    toks = jnp.zeros((1, 8), i32)
    lowered = {
        "serve_prefill": eng._get_prefill(8).lower(p, toks, i32(3)),
        "serve_prefill_chunk": eng._get_chunk(8).lower(
            p, toks, i32(0), eng._slot_cache(0, 0)),
        "serve_decode": eng._decode.lower(p, *eng._place()),
        "serve_decode_drill": eng._decode_drill.lower(
            p, *eng._place(), i32(0), jnp.float32(0.0)),
    }
    regex = harness.load_module(METRICS / "prefill_mfu.py",
                                "test_metric_prefill_mfu").PREFILL_MODULE
    for name, low in lowered.items():
        module = low.as_text().split("module @", 1)[1].split(" ", 1)[0]
        assert module == "jit_" + name
        counted = bool(regex.search(f"{module}(1234567)"))
        assert counted == name.startswith("serve_prefill"), name
