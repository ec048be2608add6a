"""Median queue wait of the requests admitted in the window: the
``queue_wait_ms`` of the engine's ``serve/admit`` spans, from a request's
scheduled arrival to the start of its admission."""
import statistics

from bench import program_spans


def read(ctx):
    if ctx.trace is None:
        return None
    got = program_spans.attrs(ctx.trace, "serve/admit")
    if not got:
        return None
    due = len(ctx.stats.get("ttft_s", ()))
    ctx.notes.append(f"{ctx.metric}: {len(got)} admissions; "
                     f"{due - len(got)} of the {due} requests due never "
                     "admitted")
    return statistics.median(float(a["queue_wait_ms"]) for a in got)
