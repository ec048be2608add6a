"""Model FLOP/s utilization of training: forward and backward FLOPs of the
window's tokens (no credit for recomputation) per second of the window,
over the chip's bf16 peak."""


def read(ctx):
    st = ctx.stats
    if not st.get("steps"):
        return None
    flops = st["steps"] * ctx.decoder.train_flops(st["batch"], st["seq"])
    return 100.0 * flops / st["window_s"] / ctx.peak_flops
