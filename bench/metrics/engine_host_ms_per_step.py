"""Host time of the serve engine per decode step: the driver's wall around
each engine step, less the engine's own decode and prefill timers (page
write-back, gathers, admission scatters, scrubs, bookkeeping)."""


def read(ctx):
    st = ctx.stats
    steps = st.get("decode_steps", 0)
    if not st.get("call_walls_s") or not steps:
        return None
    host = (sum(st["call_walls_s"]) - sum(st["decode_step_s"])
            - st["prefill_s"])
    return 1e3 * host / steps
