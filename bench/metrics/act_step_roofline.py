"""The act step's share of its roofline: the least time one act step
needs (every weight read once, the live keys and values read and one
position written, against peak HBM bandwidth; or its operations against
peak FLOP/s, whichever is longer) over the device time of the fused
actor-step program per call.  Read where the configuration states what
an act step needs (``act_step_cost``)."""

PROGRAM = r"_device_act_step_fn"


def read(ctx):
    cost = getattr(ctx.cfg_module, "act_step_cost", None)
    if cost is None:
        return None
    total, calls = ctx.trace.module_time(ctx.actor_ids[0], PROGRAM)
    if not calls:
        return None
    flops, nbytes = cost(ctx.cfg, ctx.traffic)
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (total / calls / 1e9)
