"""The share of the traced window in which no operation ran on a chip,
averaged over the cell's chips (busy time is the union of the chip's
operations)."""


def read(ctx):
    w = ctx.trace.window_ns
    if w <= 0:
        return None
    busy = [ctx.trace.busy_ns(d) for d in ctx.device_ids]
    return 100.0 * (1.0 - sum(busy) / len(busy) / w)
