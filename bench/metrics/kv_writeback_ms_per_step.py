"""Host time per decode step writing each live slot's new token back into
its KV pages (the engine's ``serve/kv_writeback`` spans); a note gives the
one-token page writes and blocking device-to-host reads per step, and how
much of ``engine_host_ms_per_step`` the engine's spans account for."""
from bench import program_spans


def read(ctx):
    v = program_spans.ms_per(ctx, "serve/kv_writeback", "decode_steps",
                             sums=("pages", "host_reads"))
    if v is not None:
        ctx.notes.append(
            f"{ctx.metric}: engine spans outside the decode and prefill "
            f"spans {program_spans.outside_timed_ms_per_step(ctx)!r} ms "
            "per decode step")
    return v
