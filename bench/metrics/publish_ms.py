"""Host time of one parameter publish: the mean duration of the
``sebulba.learner.publish`` spans that lie wholly inside the traced
window (the learner thread dispatching the copies of the params to every
actor chip, or copying them leaf by leaf where actor and learner share a
chip)."""

from bench import spans

SPAN = "sebulba.learner.publish"


def read(ctx):
    t = ctx.trace
    inside = [e - s for s, e in spans.spans(t, SPAN)
              if s >= t.lo and e <= t.hi]
    return sum(inside) / len(inside) / 1e6 if inside else None
