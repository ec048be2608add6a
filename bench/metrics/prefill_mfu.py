"""Model FLOP/s utilization of serving's prefill: the model FLOPs of the
prompt positions prefilled in the window (`bench.flops`, causal attention
included, padding not) over the device time of the prefill and chunked
prefill programs, at the bf16 peak.  The programs are recognised by the
HLO module names the engine's prefill and chunk functions give them."""
import re

# the engine jits its prefill as a lambda and its chunk program as `fn`
PREFILL_MODULE = re.compile(r"^jit_(_lambda|fn)(\(|$)|prefill|chunk")


def read(ctx):
    tr, st = ctx.trace, ctx.stats
    if tr is None or not st.get("prefill_tokens") or st.get("prefix_hits"):
        return None
    t = sum(d.module_time_s(PREFILL_MODULE.search) for d in tr.devices)
    if not t:
        return None
    work = sum(ctx.decoder.forward_flops(a, b)
               for a, b in st["prefill_tokens"])
    return 100.0 * work / t / ctx.peak_flops
