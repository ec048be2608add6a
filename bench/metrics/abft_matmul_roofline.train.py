"""Roofline share of the fused ABFT matmul kernel in the training step:
the least time its calls could take (each call the larger of its FLOPs at
the bf16 peak and its algorithmic bytes at the HBM bandwidth, counted
from the shapes in its HLO text) over the device time of those calls."""
import re

from bench import flops
from bench.trace import gemm_of

KERNEL = re.compile(r"^%?abft_matmul_pallas\b")


def read(ctx):
    return kernel_roofline(ctx, KERNEL)


def kernel_roofline(ctx, name):
    """The roofline share of the kernel ops whose name matches ``name``."""
    tr = ctx.trace
    if tr is None:
        return None
    t_min = t_run = 0.0
    bounds = {"compute": 0, "memory": 0}
    for op in tr.kernels():
        g = gemm_of(op) if name.match(op.name) else None
        if g is None:
            continue
        work = flops.gemm_flops(g.m, g.k, g.n) + \
            flops.checksum_epilogue_flops(g.m, g.n, g.f)
        nbytes = flops.gemm_bytes(g.m, g.k, g.n, g.in_bytes, g.out_bytes,
                                  accumulate=g.accumulate)
        t, bound = flops.roofline_seconds(work, nbytes, ctx.peak_flops,
                                          ctx.peak_bw)
        t_min += t
        t_run += op.dur_ns / 1e9
        bounds[bound] += 1
    if not t_run:
        return None
    ctx.notes.append(f"{ctx.metric}: {bounds['compute']} compute-bound and "
                     f"{bounds['memory']} memory-bound kernel calls")
    return 100.0 * t_min / t_run
