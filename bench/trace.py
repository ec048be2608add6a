"""The reduction from a profiler trace (``.xplane.pb``) to device metrics.

A traced run records the whole measured window with `jax.profiler`; the
window is the host span ``bench/window`` that the harness opens around it.
From the trace this module takes, per device plane:

  * busy time: the union of the intervals in which an operation ran on the
    device (ops of the "XLA Ops" line), clipped to the window;
  * the operations, by name and kind (kernel, collective, other; while
    loops and conditionals are containers of other ops and left out), so
    that a reader can sum a kernel's or the collectives' device time;
  * idle gaps, split by the innermost benchmark host span (``bench/...``,
    ``serve/...``, ``train/...``) open while the device waited;
  * for a kernel op, the matrix product it computes (`gemm_of`), from
    which a reader counts its operations and bytes with `bench.flops`;
  * the programs (HLO modules) that ran, by name.

A TPU trace names each device op by its HLO text.  Nothing here knows the
program: kernels are recognised by their ``tpu_custom_call`` target,
collectives by their opcode, products by the shapes in the text.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"(async-)?collective)", re.I)
HOST_SPAN = re.compile(r"^(bench|serve|train)/")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    name: str
    start_ns: int
    end_ns: int
    kind: str                  # "kernel" | "collective" | "other"

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                   r"\[([\d,]*)\]")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in SHAPE.findall(text)]


@dataclasses.dataclass(frozen=True)
class Gemm:
    """The matrix product a kernel call computes, read from its HLO text:
    C [m, n] = A [m, k] @ B [k, n], with ``f`` checksum rows and columns
    and an accumulated C when an operand has C's shape."""
    m: int
    k: int
    n: int
    f: int
    in_bytes: int
    out_bytes: int
    accumulate: bool


def gemm_of(op: "Op") -> Optional[Gemm]:
    """The product of a kernel op (a ``custom-call``), or None when its HLO
    text holds no product."""
    text = op.name
    if "custom-call(" not in text:
        return None
    head, args = text.split("custom-call(", 1)
    outs = [s for s in _shapes(head) if len(s[1]) == 2]
    ins = [s for s in _shapes(args.split("custom_call_target", 1)[0])
           if len(s[1]) == 2]
    if not outs or not ins:
        return None
    out_dt, (m, n) = outs[0]
    acc = any(shape == (m, n) for _, shape in ins)
    for i, (dt_a, (ma, ka)) in enumerate(ins):
        if ma != m:
            continue
        for j, (_, (kb, nb)) in enumerate(ins):
            if j != i and kb == ka and nb == n:
                # the checksum weights W_m [f, m]: f is small
                f = next((s[0] for _, s in ins if s[1] == m and s[0] <= 16),
                         0)
                return Gemm(m, ka, n, f, DTYPE_BYTES[dt_a],
                            DTYPE_BYTES[out_dt], acc)
    return None


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Op]                        # leaf ops (not while/conditional)
    modules: List[Tuple[int, int, str]]  # program executions
    busy_ns: int
    gaps: List[Tuple[int, int]]          # idle intervals inside the window

    def module_time_s(self, pred) -> float:
        return sum(e - s for s, e, n in self.modules if pred(n)) / 1e9

    def time_s(self, pred) -> float:
        return sum(o.dur_ns for o in self.ops if pred(o)) / 1e9


@dataclasses.dataclass
class Summary:
    window: Tuple[int, int]
    devices: List[Device]
    spans: List[Tuple[int, int, str]]    # host spans (start, end, name)
    _segments: Optional[List] = dataclasses.field(default=None, repr=False)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy time averaged over the devices used."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self, device: Device) -> float:
        return 1.0 - device.busy_ns / max(self.window[1] - self.window[0], 1)

    def kernels(self, device: Optional[Device] = None) -> List[Op]:
        devs = [device] if device is not None else self.devices
        return [o for d in devs for o in d.ops if o.kind == "kernel"]

    def segments(self) -> List[Tuple[int, int, str]]:
        """The host timeline as disjoint segments, each named by the
        innermost benchmark span open in it."""
        if self._segments is None:
            edges = sorted({t for s, e, _ in self.spans for t in (s, e)})
            by_start = sorted(self.spans)
            segs, active, j = [], [], 0
            for a, b in zip(edges, edges[1:]):
                while j < len(by_start) and by_start[j][0] <= a:
                    active.append(by_start[j])
                    j += 1
                active = [sp for sp in active if sp[1] > a]
                if active:   # the innermost: latest start, earliest end
                    segs.append((a, b, max(
                        active, key=lambda sp: (sp[0], -sp[1]))[2]))
            self._segments = segs
        return self._segments

    def idle_by_span(self, device: Device) -> Dict[str, int]:
        """A device's idle nanoseconds, split by the host span open."""
        segs = self.segments()
        starts = [s for s, _, _ in segs]
        out: Dict[str, int] = defaultdict(int)
        for g0, g1 in device.gaps:
            covered = 0
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(segs) and segs[i][0] < g1:
                a, b = max(segs[i][0], g0), min(segs[i][1], g1)
                if b > a:
                    out[segs[i][2]] += b - a
                    covered += b - a
                i += 1
            if covered < g1 - g0:
                out["outside any span"] += g1 - g0 - covered
        return out

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """Device operations that took most time, and idle time by what the
        host was doing, summed over the devices used."""
        by_op: Dict[str, int] = defaultdict(int)
        for d in self.devices:
            for o in d.ops:
                by_op[_op_family(o)] += o.dur_ns
        by_span: Dict[str, int] = defaultdict(int)
        for d in self.devices:
            for k, v in self.idle_by_span(d).items():
                by_span[k] += v
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9-]*)\(")
NAME = re.compile(r"^%?([A-Za-z_][\w-]*?)(\.\d+)?( =|$)")
CONTAINERS = {"while", "conditional", "call"}


def opcode(text: str) -> str:
    """The HLO opcode of an op whose trace name is its HLO text (TPU), or
    the name itself."""
    m = OPCODE.search(text)
    return m.group(1) if m else text


def _op_family(o: Op) -> str:
    """An op's HLO instruction name without its instance number."""
    m = NAME.match(o.name)
    return m.group(1) if m else o.name[:60]


def _kind(name: str) -> str:
    if "tpu_custom_call" in name:
        return "kernel"
    code = opcode(name)
    if COLLECTIVE.match(code) or COLLECTIVE.match(name):
        return "collective"
    if code in CONTAINERS:
        return "container"
    return "other"


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_planes(planes) -> List:
    """Planes of accelerator cores, in device order."""
    devs = [p for p in planes
            if re.match(r"^/device:(TPU|GPU):\d+$", p.name)]
    return sorted(devs, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def reduce(profile, window_name: str = "bench/window",
           devices: Optional[int] = None) -> Summary:
    """Reduce a `jax.profiler.ProfileData` to a `Summary`."""
    planes = list(profile.planes)
    spans: List[Tuple[int, int, str]] = []
    window = None
    # ProfileData gives float nanoseconds; keep integers
    for p in planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name == window_name:
                    window = (int(ev.start_ns), int(ev.end_ns))
                if HOST_SPAN.match(ev.name):
                    spans.append((int(ev.start_ns), int(ev.end_ns), ev.name))
    if window is None:
        raise ValueError(f"no host span {window_name!r} in the trace")
    w0, w1 = window
    devs = device_planes(planes)
    if devices is not None:
        devs = devs[:devices]
    out = []
    for p in devs:
        ops, modules = [], []
        for line in p.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s = max(int(ev.start_ns), w0)
                e = min(int(ev.end_ns), w1)
                if e <= s:
                    continue
                if line.name == MODULES_LINE:
                    modules.append((s, e, ev.name))
                    continue
                kind = _kind(ev.name)
                if kind != "container":
                    ops.append(Op(ev.name, s, e, kind))
        busy = _union((o.start_ns, o.end_ns) for o in ops)
        gaps, t = [], w0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < w1:
            gaps.append((t, w1))
        out.append(Device(p.name, ops, modules, sum(e - s for s, e in busy),
                          gaps))
    return Summary(window=window, devices=out, spans=spans)


def start() -> str:
    """Start the profiler into a fresh directory under ``$TMPDIR``."""
    import jax
    d = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(d)
    return d


def stop():
    import jax
    jax.profiler.stop_trace()


def xplane_file(d: str) -> str:
    files = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return max(files, key=os.path.getmtime)


def reduce_file(path: str, **kw) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), **kw)


def reduce_dir(d: str, **kw) -> Summary:
    return reduce_file(xplane_file(d), **kw)


def cleanup(d: str):
    shutil.rmtree(d, ignore_errors=True)
