"""Device time of the fused actor-step program per call, on the actor chip
(the first actor chip): the jitted ``Sebulba._device_act_step_fn``."""

PROGRAM = r"_device_act_step_fn"


def read(ctx):
    total, calls = ctx.trace.module_time(ctx.actor_ids[0], PROGRAM)
    return total / calls / 1e6 if calls else None
