"""The program's own spans in a traced window.

The serving engine opens its spans (`repro.obs.span`) as profiler
annotations too, so the reduced trace holds each one's interval on the
device trace's clock (`bench.trace.Summary.spans`).  Their attrs are read
from the program's event bus (`repro.obs.events()`), which holds the same
spans in the same order: the harness reads the metrics after the window
and before anything else runs the program, so the last k spans of a name
on the bus are the k spans of that name that opened in the window.  On a
program without such spans every function here finds nothing, and the
readers return None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench.trace import _union

# the engine's spans directly under one engine step; `serve/kv_write`,
# `serve/scrub_kv` and `serve/scrub_params` nest inside them
ENGINE = ("serve/scrub", "serve/kv_gather", "serve/decode_step",
          "serve/kv_writeback", "serve/emit", "serve/admit",
          "serve/prefill", "serve/prefill_chunk")
# the spans the engine's own decode and prefill timers cover
TIMED = ("serve/decode_step", "serve/prefill", "serve/prefill_chunk")


def in_window(trace, names) -> List[Tuple[int, int]]:
    """Intervals (ns) of the spans named in ``names`` that opened in the
    window, clipped to it."""
    w0, w1 = trace.window
    return [(s, min(e, w1)) for s, e, n in trace.spans
            if n in names and w0 <= s < w1]


def _covered(intervals) -> int:
    return sum(e - s for s, e in _union(intervals))


def total_s(trace, name: str) -> float:
    return sum(e - s for s, e in in_window(trace, (name,))) / 1e9


def attrs(trace, name: str) -> Optional[List[Dict]]:
    """The attrs of the spans of ``name`` that opened in the window, from
    the program's bus; None where the bus does not hold them all."""
    k = len(in_window(trace, (name,)))
    if not k:
        return None
    from repro import obs
    if obs.dropped():
        return None
    evs = [e for e in obs.events() if e.kind == "span" and e.name == name]
    if len(evs) < k:
        return None
    return [e.attrs for e in evs[-k:]]


def ms_per(ctx, name: str, unit: str, sums=()) -> Optional[float]:
    """Milliseconds of the ``name`` spans in the window per ``unit`` (a
    count in the driver's stats), with each attr in ``sums`` totalled per
    unit in a note."""
    tr, n = ctx.trace, ctx.stats.get(unit)
    if tr is None or not n or not in_window(tr, (name,)):
        return None
    if sums:
        got = attrs(tr, name)
        if got is not None:
            per = ", ".join(f"{k} {sum(a.get(k, 0) for a in got) / n!r}"
                            for k in sums)
            ctx.notes.append(f"{ctx.metric}: {len(got)} {name} spans; "
                             f"{per} per {unit[:-1]}")
    return 1e3 * total_s(tr, name) / n


def outside_timed_ms_per_step(ctx) -> Optional[float]:
    """Milliseconds per decode step that the engine's spans cover outside
    its decode and prefill spans: the part of ``engine_host_ms_per_step``
    that the spans account for."""
    tr, n = ctx.trace, ctx.stats.get("decode_steps")
    if tr is None or not n:
        return None
    return 1e3 * (_covered(in_window(tr, ENGINE)) -
                  _covered(in_window(tr, TIMED))) / 1e9 / n
