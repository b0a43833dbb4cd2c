"""Device time of the learners' gradient exchange per update, on the
slowest learner chip: the collective operations on the chip (the
update's all-reduces) over the update programs that ran there.  Read
where the learners span several chips, so that they exchange."""


def read(ctx):
    if len(ctx.learner_ids) < 2:
        return None
    per_chip = []
    for dev in ctx.learner_ids:
        coll, events = ctx.trace.collective_time(dev)
        _, updates = ctx.trace.module_time(dev, r"^jit_update$")
        if events and updates:
            per_chip.append(coll / updates / 1e6)
    return max(per_chip) if per_chip else None
