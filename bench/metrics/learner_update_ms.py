"""Device time of the learner's donated update program per update, on the
slowest learner chip: the jitted ``update`` that ``Sebulba._get_update``
builds."""

PROGRAM = r"^jit_update$"


def read(ctx):
    per_chip = []
    for dev in ctx.learner_ids:
        total, calls = ctx.trace.module_time(dev, PROGRAM)
        if calls:
            per_chip.append(total / calls / 1e6)
    return max(per_chip) if per_chip else None
