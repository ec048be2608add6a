"""Roofline share of the grouped expert-product kernels in MoE training:
the least time of the expert work the window's steps ran through them
(`bench.flops_moe.MoEDecoder.expert_passes`: gate/up and down over the
chip's expected share of the assignments, with their checksum columns;
each pass the larger of its FLOPs at the bf16 peak and its bytes at the
HBM bandwidth) over the device time of the trace ops the pattern matches.

On a v5e the forward and its recomputation and dX run as
``abft_grouped_matmul_pallas`` ops and dW as
``abft_grouped_matmul_pallas_dw``: the pattern matches all four passes,
and all four are counted.  A program without these kernels has no such
ops, and the metric reads nothing."""
import re

from bench.flops_moe import MoEDecoder

KERNEL = re.compile(r"^%?abft_grouped_matmul_pallas(_dw)?\b")
PASSES = ("forward", "recompute", "dx", "dw")


def read(ctx):
    tr, st = ctx.trace, ctx.stats
    if tr is None or not st.get("steps"):
        return None
    t_run = sum(op.dur_ns for op in tr.kernels() if KERNEL.match(op.name))
    if not t_run:
        return None
    dims = MoEDecoder.from_config(ctx.cell.config)
    t_min = st["steps"] * dims.expert_min_seconds(
        st["batch"], st["seq"], ctx.peak_flops, ctx.peak_bw, PASSES)
    return 100.0 * t_min / (t_run / 1e9)
