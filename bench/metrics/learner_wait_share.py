"""The share of the learner's time spent waiting for a trajectory: the
``sebulba.learner.get`` spans (each get from the queue, those that time
out included) over the window in which the learner's spans were seen,
from the start of its first span to the end of its last
(``spans.learner_window``; a wait that straddles the capture's start or
end is not recorded)."""

from bench import spans

SPAN = "sebulba.learner.get"


def read(ctx):
    win = spans.learner_window(ctx.trace)
    if win is None:
        return None
    lo, hi = win
    waits = spans.clip(spans.spans(ctx.trace, SPAN), lo, hi)
    return 100.0 * sum(e - s for s, e in waits) / (hi - lo)
