"""The Pallas SSD scan's share of its roofline on the learner chips: the
least time one call of the kernel needs (the configuration's
``ssd_scan_cost``: its operations against peak FLOP/s or its bytes against
peak HBM bandwidth, whichever is longer) over the kernel's device time per
call; the lowest chip.  Read where the configuration states the kernel's
cost and the kernel ran (the learner's forward, and its recomputation in
the backward pass)."""

KERNEL = r"^%?ssd_scan(\.\d+)?( = |$)"


def read(ctx):
    cost = getattr(ctx.cfg_module, "ssd_scan_cost", None)
    if cost is None:
        return None
    flops, nbytes = cost(ctx.cfg, ctx.traffic)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    shares = []
    for dev in ctx.learner_ids:
        total, calls = ctx.trace.op_time(dev, KERNEL)
        if calls:
            shares.append(100.0 * least / (total / calls / 1e9))
    return min(shares) if shares else None
