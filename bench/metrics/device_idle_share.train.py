"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window); on several chips, that of the
busiest one."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    return 100.0 * min(tr.idle_share(d) for d in tr.devices)
