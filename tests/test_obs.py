"""The unified telemetry bus: spans, metrics, exporters, integration.

Four layers, matching ``src/repro/obs/``:

  * span nesting/ordering semantics — including exits via exceptions and
    leaked inner spans (ordering must stay consistent, errors must never
    be swallowed, ``ok=False`` must be recorded);
  * counter/gauge/histogram determinism — two identical runs against
    fresh registries produce byte-identical Prometheus snapshots;
  * exporter goldens — the Perfetto document validates against the
    trace_event schema subset we emit, the Prometheus text round-trips
    through `parse_prometheus`, the JSONL log round-trips events
    loss-free;
  * the drilled-serve integration — an SDC drill through `ServeEngine`
    with the bus on must tell the SAME story on the bus as in
    `EngineStats` (counts, locations, rungs), and `lifecycles` must fold
    the stream into a complete inject -> detect -> rung -> verdict.
"""
import json

import pytest

from repro import obs
from repro.obs import export, metrics
from repro.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _fresh_bus():
    """Every test starts from an empty buffer + registry and leaves the
    process-global bus the way tier-1 expects it (enabled, no leftover
    subscribers from this module)."""
    obs.reset_all()
    obs.enable(True)
    yield
    obs.reset_all()
    obs.enable(True)


# ---------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------

def test_span_nesting_records_inner_before_outer():
    with obs.span("outer", step=3):
        with obs.span("inner"):
            pass
    names = [e.name for e in obs.events()]
    assert names == ["inner", "outer"]          # inner closes first
    inner, outer = obs.events()
    assert inner.parent == "outer" and outer.parent is None
    assert inner.step == 3 or inner.step is None  # explicit step on outer only
    assert outer.step == 3
    assert inner.ok and outer.ok
    assert outer.dur_s >= inner.dur_s >= 0.0


def test_span_exception_not_swallowed_and_marked():
    with pytest.raises(RuntimeError, match="boom"):
        with obs.span("outer"):
            with obs.span("inner"):
                raise RuntimeError("boom")
    inner, outer = obs.events()
    assert [inner.name, outer.name] == ["inner", "outer"]
    assert not inner.ok and not outer.ok


def test_leaked_inner_span_does_not_corrupt_ordering():
    # an inner span entered but never exited (e.g. a generator abandoned
    # mid-iteration): the outer exit pops past it and stays consistent
    outer = obs.span("outer")
    inner = obs.span("inner")
    outer.__enter__()
    inner.__enter__()
    outer.__exit__(None, None, None)
    with obs.span("after"):
        pass
    ev = obs.events()
    assert [e.name for e in ev] == ["outer", "after"]
    assert ev[1].parent is None                  # stack fully unwound


def test_first_occurrence_flag_and_step_clock():
    obs.set_step(7)
    with obs.span("train/step"):
        pass
    with obs.span("train/step"):
        pass
    a, b = obs.events()
    assert a.first and not b.first
    assert a.step == b.step == 7


def test_disabled_with_no_subscribers_records_nothing():
    obs.enable(False)
    with obs.span("x"):
        obs.event("y")
    assert obs.events() == []


def test_subscribers_fire_even_while_disabled():
    got = []
    sub = obs.subscribe(got.append)
    try:
        obs.enable(False)
        obs.event("straggler/feed", walls=[1.0, 2.0])
        assert [e.name for e in got] == ["straggler/feed"]
        assert obs.events() == []                # buffer stayed off
    finally:
        obs.unsubscribe(sub)


class _Recorder:
    """An annotator that logs each annotation's opening and closing."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **attrs):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.log.append(("enter", name, attrs))

            def __exit__(self, exc_type, exc, tb):
                rec.log.append(("exit", name, exc_type))
        return _Ann()


def test_annotator_wraps_each_span_with_name_and_attrs():
    tr, rec = Tracer(), _Recorder()
    tr.annotator = rec
    with tr.span("serve/admit", rid=3, queue_wait_ms=1.5):
        with tr.span("serve/prefill", step=4, bucket=16):
            pass
    assert rec.log == [
        ("enter", "serve/admit", {"rid": 3, "queue_wait_ms": 1.5}),
        ("enter", "serve/prefill", {"bucket": 16, "step": 4}),
        ("exit", "serve/prefill", None),
        ("exit", "serve/admit", None)]


def test_annotator_exits_on_exception():
    tr, rec = Tracer(), _Recorder()
    tr.annotator = rec
    with pytest.raises(RuntimeError, match="boom"):
        with tr.span("outer", a=1):
            raise RuntimeError("boom")
    assert rec.log == [("enter", "outer", {"a": 1}),
                       ("exit", "outer", RuntimeError)]
    (ev,) = tr.events()
    assert not ev.ok


def test_no_annotator_calls_nothing_and_instants_stay_on_the_bus():
    tr, rec = Tracer(), _Recorder()
    with tr.span("x", a=1):
        tr.event("fault/detect")
    assert tr.annotator is None and rec.log == []
    tr.annotator = rec
    tr.event("fault/inject")
    tr.recovery("scrub:restore", 0.01)
    assert rec.log == []
    tr.reset()
    assert tr.annotator is rec                   # survives a reset


def test_span_dur_s_set_on_exit_even_when_disabled():
    tr = Tracer()
    tr.enable(False)
    sp = tr.span("x")
    assert sp.dur_s is None
    with sp:
        pass
    assert sp.dur_s is not None and sp.dur_s >= 0.0
    assert tr.events() == []
    tr.enable(True)
    with tr.span("y") as sp:
        pass
    assert tr.events()[0].dur_s == sp.dur_s


def test_profiler_install_sets_trace_annotation():
    import jax
    from repro.obs import profiler
    saved = obs.TRACER.annotator
    try:
        profiler.install()
        profiler.install()
        assert obs.TRACER.annotator is jax.profiler.TraceAnnotation
        with obs.span("serve/emit", active=2) as sp:
            pass
        assert sp.dur_s is not None
    finally:
        obs.TRACER.annotator = saved


def test_trace_module_imports_only_the_stdlib():
    import ast
    import sys
    from repro.obs import trace
    tree = ast.parse(open(trace.__file__).read())
    mods = {a.name.split(".")[0] for n in ast.walk(tree)
            if isinstance(n, ast.Import) for a in n.names}
    mods |= {n.module.split(".")[0] for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n.level == 0}
    mods.discard("__future__")
    assert mods <= set(sys.stdlib_module_names), mods


def test_bounded_buffer_counts_drops():
    tr = Tracer(max_events=3)
    for i in range(5):
        tr.event("e%d" % i)
    assert len(tr.events()) == 3
    assert tr.dropped() == 2


# ---------------------------------------------------------------------
# timeline folds
# ---------------------------------------------------------------------

def test_rung_timeline_warm_compile_split():
    tr = Tracer()
    tr.recovery("diskless", 1.0)                       # first -> first_trace
    tr.recovery("diskless", 0.2)                       # warm by position
    tr.recovery("elastic:disk", 3.0, warm_s=0.5, compile_s=2.5)
    tl = obs.rung_timeline(tr.events())
    d = tl["diskless"]
    assert d["n"] == 2
    assert d["first_trace"]["n"] == 1 and d["first_trace"]["mean_s"] == 1.0
    assert d["warm"]["n"] == 1 and d["warm"]["mean_s"] == 0.2
    e = tl["elastic:disk"]
    assert e["warm"] == {"n": 1, "mean_s": 0.5, "p50_s": 0.5,
                         "p95_s": 0.5, "max_s": 0.5}
    assert e["compile_s"] == 2.5                 # explicit split preferred
    assert e["first_trace"]["n"] == 0


def test_lifecycles_fifo_and_fault_id_pairing():
    tr = Tracer()
    tr.event("fault/inject", surface="a")
    tr.event("fault/inject", surface="b", fault_id="B")
    tr.event("fault/detect", detector="x")             # FIFO -> inject a
    tr.event("fault/detect", fault_id="B")
    tr.recovery("scrub:restore", 0.01, fault_id="B")
    tr.recovery("abft_inflight", 0.002)
    tr.event("fault/verdict", verdict="bit_identical", fault_id="B")
    lcs = obs.lifecycles(tr.events())
    by_surface = {lc["inject"]["surface"]: lc for lc in lcs}
    a, b = by_surface["a"], by_surface["b"]
    assert b["rungs"][0]["rung"] == "scrub:restore"
    assert b["verdict"]["verdict"] == "bit_identical"
    assert b["complete"] and b["mttr_s"] == pytest.approx(0.01)
    assert a["rungs"][0]["rung"] == "abft_inflight"
    assert a["complete"] and a["verdict"] is None
    assert a["detect_latency_s"] >= 0.0


def test_percentile_interpolates():
    xs = [0.0, 1.0, 2.0, 3.0]
    assert obs.percentile(xs, 0) == 0.0
    assert obs.percentile(xs, 100) == 3.0
    assert obs.percentile(xs, 50) == pytest.approx(1.5)
    assert obs.percentile([5.0], 95) == 5.0
    assert obs.percentile([], 50) == 0.0


# ---------------------------------------------------------------------
# metrics determinism
# ---------------------------------------------------------------------

def _drive(reg: metrics.Registry):
    reg.counter("repro_detections_total", "trips").inc(surface="serve")
    reg.counter("repro_detections_total").inc(2.0, surface="train")
    reg.gauge("repro_queue_depth", "depth").set(4)
    h = reg.histogram("repro_checksum_verify_seconds", "walls")
    for v in (1e-4, 2e-3, 0.7, 1e-4):
        h.observe(v, domain="serve")
    return reg


def test_identical_runs_snapshot_identically():
    a, b = _drive(metrics.Registry()), _drive(metrics.Registry())
    assert json.dumps(a.snapshot()) == json.dumps(b.snapshot())
    assert export.to_prometheus(a) == export.to_prometheus(b)


def test_counter_monotone_and_type_conflicts():
    reg = metrics.Registry()
    c = reg.counter("x_total")
    with pytest.raises(ValueError):
        c.inc(-1.0)
    assert reg.counter("x_total") is c           # get-or-create
    with pytest.raises(ValueError):
        reg.gauge("x_total")                     # name is a counter


def test_histogram_cumulative_buckets():
    reg = metrics.Registry()
    h = reg.histogram("w_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot_one()
    assert snap["cumulative"] == [1, 2, 3]       # le=0.1, le=1.0, +Inf
    assert snap["count"] == 3
    assert snap["sum"] == pytest.approx(5.55)


# ---------------------------------------------------------------------
# exporter goldens
# ---------------------------------------------------------------------

def test_prometheus_round_trip():
    reg = _drive(metrics.Registry())
    text = export.to_prometheus(reg)
    parsed = export.parse_prometheus(text)
    det = parsed["repro_detections_total"]
    assert det["type"] == "counter" and det["help"] == "trips"
    vals = {s["labels"]["surface"]: s["value"] for s in det["samples"]}
    assert vals == {"serve": 1.0, "train": 2.0}
    hist = parsed["repro_checksum_verify_seconds"]
    assert hist["type"] == "histogram"
    count = [s for s in hist["samples"]
             if s["name"].endswith("_count")][0]["value"]
    assert count == 4
    inf_bucket = [s for s in hist["samples"]
                  if s["labels"].get("le") == "+Inf"][0]["value"]
    assert inf_bucket == 4


def test_perfetto_schema_golden():
    with obs.span("serve/run_trace", n_requests=2):
        obs.event("fault/inject", step=1, surface="s")
        obs.recovery("abft_inflight", 0.01, warm_s=0.01, compile_s=0.0)
    doc = export.to_perfetto(obs.events())
    assert export.validate_perfetto(doc) == 3    # non-metadata events
    assert doc["otherData"]["schema"] == export.EVENT_SCHEMA
    json.dumps(doc)                              # serializable
    by_name = {e["name"]: e for e in doc["traceEvents"]
               if e.get("ph") != "M"}
    run = by_name["serve/run_trace"]
    assert run["ph"] == "X" and run["dur"] >= 0 and run["cat"] == "serve"
    inj = by_name["fault/inject"]
    assert inj["ph"] == "i" and inj["s"] == "t" and inj["args"]["step"] == 1
    rec = by_name["recovery/abft_inflight"]
    assert rec["ph"] == "X" and rec["args"]["warm_s"] == 0.01
    # metadata names the process and every mapped thread
    metas = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert {m["name"] for m in metas} == {"process_name", "thread_name"}


def test_perfetto_validator_rejects_bad_docs():
    with pytest.raises(ValueError):
        export.validate_perfetto({"not": "a trace"})
    with pytest.raises(ValueError):
        export.validate_perfetto({"traceEvents": [
            {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0.0}]})
    with pytest.raises(ValueError):              # negative ts
        export.validate_perfetto({"traceEvents": [
            {"ph": "i", "name": "x", "pid": 1, "tid": 1, "ts": -1.0,
             "s": "t"}]})


def test_jsonl_round_trip(tmp_path):
    obs.set_step(11)
    with obs.span("train/step", gen=0):
        obs.event("fault/detect", detector="abft_psum", row=3)
    path = tmp_path / "events.jsonl"
    export.write_jsonl(str(path), obs.events())
    back = export.read_jsonl(str(path))
    assert [(e.name, e.kind, e.step, e.seq, e.attrs) for e in back] == \
        [(e.name, e.kind, e.step, e.seq, e.attrs) for e in obs.events()]
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"schema": "other/v9"}\n')
        export.read_jsonl(str(bad))


# ---------------------------------------------------------------------
# drilled-serve integration: the bus and EngineStats tell one story
# ---------------------------------------------------------------------

def test_drilled_serve_bus_matches_engine_stats():
    import jax
    from repro.configs.base import smoke_config
    from repro.ft.failures import SDCInjector, SDCPlan
    from repro.models import transformer as tf
    from repro.serve.engine import Request, ServeEngine

    cfg = smoke_config("qwen2-0.5b")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=32,
                      abft_reduce="correct",
                      sdc=SDCInjector(SDCPlan(((2, 0, 1e4),))))
    obs.reset_all()
    for i in range(2):
        eng.submit(Request(rid=i, prompt=[3 + i, 5, 7], max_new_tokens=4))
    eng.run()
    st = eng.stats
    assert st.detections == 1 and st.corrections == 1, st

    evs = obs.events()
    injects = [e for e in evs if e.name == "fault/inject"]
    detects = [e for e in evs if e.name == "fault/detect"]
    rungs = [e for e in evs if e.name == "recovery/abft_inflight"]
    assert len(injects) == len(st.events) == 1
    assert len(detects) == st.detections
    assert len(rungs) == st.corrections
    # located the same element the engine recorded
    assert detects[0].attrs["row"] == st.events[0].row
    assert detects[0].attrs["col"] == st.events[0].col
    assert rungs[0].attrs["warm_s"] == pytest.approx(
        st.events[0].recovery_s)

    lcs = obs.lifecycles(evs)
    done = [lc for lc in lcs if lc["complete"]]
    assert len(done) == 1
    assert done[0]["rungs"][0]["rung"] == "abft_inflight"
    assert done[0]["mttr_s"] == pytest.approx(st.events[0].recovery_s)

    # the metrics side agrees too
    assert obs.counter("repro_detections_total").total() >= 1
    assert obs.counter("repro_corrections_total").total() >= 1
    assert obs.counter("repro_decode_steps_total").total() == \
        st.decode_steps
