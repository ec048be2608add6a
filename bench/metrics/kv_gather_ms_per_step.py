"""Host time per decode step gathering the KV pages into the dense cache
that the decode program reads (the engine's ``serve/kv_gather`` spans).
The gather does not wait for the device: its device time shows inside the
decode step."""
from bench import program_spans


def read(ctx):
    return program_spans.ms_per(ctx, "serve/kv_gather", "decode_steps")
