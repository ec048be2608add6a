"""Mean of the engine's own decode-step timer (`EngineStats.decode_step_s`:
the compiled decode and its argmax, waited for)."""


def read(ctx):
    xs = ctx.stats.get("decode_step_s")
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
