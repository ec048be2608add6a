"""Host time per admitted request writing its prefilled KV positions into
pages (the engine's ``serve/kv_write`` spans, inside its prefill and chunk
spans); a note gives the page writes and blocking device-to-host reads per
request."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "serve/kv_write", "prefills",
                                sums=("pages", "host_reads"))
