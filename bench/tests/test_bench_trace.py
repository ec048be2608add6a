"""The reduction from a profiler trace to busy, idle, kernel and collective
time, on a trace written out by hand and on one recorded on a v5e."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"

# times in microseconds; the window is the host span bench/window [0, 100)
HOST = [("bench/window", 0, 100), ("serve/engine_step", 0, 40),
        ("serve/idle_wait", 40, 100), ("serve/submit", 52, 53)]
# device ops named by their HLO text, as a TPU trace names them
OPS = [
    ("%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p), kind=kLoop",
     5, 15),
    ("%custom-call.2 = f32[8,128]{1,0} custom-call(f32[8,64]{1,0} %a, "
     "f32[64,128]{1,0} %b), custom_call_target=tpu_custom_call", 10, 30),
    ("%all-reduce.3 = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %c), "
     "to_apply=%add", 50, 60),
    ("%while.9 = (s32[]) while((s32[]) %t), body=%b, condition=%c", 0, 100),
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %d), kind=kLoop", 95, 120),
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %e), kind=kLoop", -20, -10),
]


def _plane(pid, name, line, rows):
    """One XPlane in text format; rows are (name, start, end) in
    microseconds after 1 ms."""
    meta, events = {}, []
    for ev, s, e in rows:
        i = meta.setdefault(ev, len(meta) + 1)
        events.append(f"events {{ metadata_id: {i} "
                      f"offset_ps: {(s + 1000) * 10**6} "
                      f"duration_ps: {(e - s) * 10**6} }}")
    metas = "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: '
                    f'"{n}" }} }}' for n, i in meta.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: {pid} '
            f'name: "{line}" timestamp_ns: 0 {" ".join(events)} }}'
            f"{metas} }}")


def _text_proto(host, ops):
    return (_plane(1, "/host:CPU", "python", host)
            + _plane(2, "/device:TPU:0", "XLA Ops", ops))


@pytest.fixture
def summary():
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_text_proto(_text_proto(HOST, OPS)))


def test_window_busy_and_idle(summary):
    assert summary.window_s == pytest.approx(100e-6)
    (dev,) = summary.devices
    # union of [5,30) (two overlapping ops), [50,60) and [95,100); the
    # while loop holds other ops and counts for nothing itself
    assert dev.busy_ns == pytest.approx(40_000)
    assert summary.busy_s == pytest.approx(40e-6)
    assert summary.idle_share(dev) == pytest.approx(0.6)
    assert [(s - 1e6, e - 1e6) for s, e in dev.gaps] == pytest.approx(
        [(0, 5e3), (30e3, 50e3), (60e3, 95e3)])


def test_kernel_and_collective_time(summary):
    (dev,) = summary.devices
    assert [o.name[:14] for o in summary.kernels()] == ["%custom-call.2"]
    assert trace.gemm_of(summary.kernels()[0]) == trace.Gemm(
        m=8, k=64, n=128, f=0, in_bytes=4, out_bytes=4, accumulate=False)
    assert dev.time_s(lambda o: o.kind == "kernel") == pytest.approx(20e-6)
    assert dev.time_s(lambda o: o.kind == "collective") == \
        pytest.approx(10e-6)


def test_idle_gaps_by_host_span(summary):
    b = summary.breakdown()
    gaps = dict(b["idle_gaps"])
    # [0,5) and [30,40) under the engine step; [40,50), [60,95) waiting
    # (the submit span [52,53) falls while the all-reduce runs)
    assert gaps == pytest.approx({"serve/engine_step": 15e-6,
                                  "serve/idle_wait": 45e-6})
    ops = dict(b["device_ops"])
    assert ops["custom-call"] == pytest.approx(20e-6)
    assert ops["fusion"] == pytest.approx(15e-6)


def test_no_window_is_an_error():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(_text_proto(HOST[1:], OPS))
    with pytest.raises(ValueError, match="bench/window"):
        trace.reduce(pd)


def test_recorded_v5e_trace():
    """Two calls of the fused ABFT matmul kernel and an XLA program on one
    v5e, inside a host span ``bench/window``; the trace names each device
    op by its HLO text."""
    s = trace.reduce_file(str(DATA / "v5e_small.xplane.pb"))
    (dev,) = s.devices
    assert dev.name == "/device:TPU:0"
    assert s.window_s == pytest.approx(0.01314695)
    assert [o.kind for o in dev.ops] == \
        ["kernel", "other", "other", "other"] * 2
    assert dev.busy_ns == 68843
    assert s.idle_share(dev) == pytest.approx(1 - 68843 / 13146950)
    assert dev.time_s(lambda o: o.kind == "kernel") == pytest.approx(
        43.765e-6)
    assert [n for _, _, n in dev.modules] == \
        ["jit__lambda(449652037105606672)",
         "jit__lambda(13846547478428857463)"] * 2
    g = trace.gemm_of(s.kernels()[0])
    assert g == trace.Gemm(m=512, k=1024, n=768, f=2, in_bytes=4,
                           out_bytes=4, accumulate=False)
    ops = dict(s.breakdown()["device_ops"])
    assert ops["abft_matmul_pallas"] == pytest.approx(43.765e-6)
