"""The share of the traced window in which a chip runs no operation while
a ``sebulba.learner.publish`` span is open on the host, averaged over the
cell's chips: the idle time that the parameter publish leaves.  It is
part of ``idle_share.train``, over the same window."""

from bench import spans
from bench.devtrace import gaps_ns

SPAN = "sebulba.learner.publish"


def read(ctx):
    t = ctx.trace
    if not spans.has_spans(t) or t.window_ns <= 0:
        return None
    publish = spans.clip(spans.spans(t, SPAN), t.lo, t.hi)
    idle = []
    for dev in ctx.device_ids:
        busy = [(s, e) for _, s, e in t.ops.get(dev, [])]
        idle.append(spans.overlap_ns(gaps_ns(busy, t.lo, t.hi), publish))
    return 100.0 * sum(idle) / len(idle) / t.window_ns
