"""The engine's prefill time per admitted request (`EngineStats.prefill_s`
over `EngineStats.prefills`), all chunks of a chunked prompt together."""


def read(ctx):
    st = ctx.stats
    if not st.get("prefills"):
        return None
    return 1e3 * st["prefill_s"] / st["prefills"]
