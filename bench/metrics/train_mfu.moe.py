"""Model FLOP/s utilization of MoE training on one chip's share: forward
and backward FLOPs of the window's steps (`bench.flops_moe`: attention,
router, the head over the vocabulary slice and the chip's expected share
of the expert work; no credit for recomputation) per second of the
window, over the chip's bf16 peak."""
from bench.flops_moe import MoEDecoder


def read(ctx):
    st = ctx.stats
    if not st.get("steps"):
        return None
    dims = MoEDecoder.from_config(ctx.cell.config)
    flops = st["steps"] * dims.train_flops(st["batch"], st["seq"])
    return 100.0 * flops / st["window_s"] / ctx.peak_flops
