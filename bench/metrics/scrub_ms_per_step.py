"""Time of the engine's at-rest scrubs (KV pages and parameters verified
against their checksums; the ``serve/scrub`` spans) per decode step of the
window."""
from bench import program_spans


def read(ctx):
    v = program_spans.ms_per(ctx, "serve/scrub", "decode_steps")
    if v is not None:
        n = len(program_spans.in_window(ctx.trace, ("serve/scrub",)))
        ctx.notes.append(f"{ctx.metric}: {n} scrubs in the window")
    return v
