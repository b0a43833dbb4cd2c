"""The share of the actor threads' time spent handing trajectories to the
learner: the ``sebulba.actor.put`` spans (a put that finds the queue full
retries inside its span) clipped to the learner's window
(``spans.learner_window``), over that window times the actor threads of
the traffic mix.  Beside ``learner_wait_share`` it says which side of the
queue sets the pace."""

from bench import spans

SPAN = "sebulba.actor.put"


def read(ctx):
    win = spans.learner_window(ctx.trace)
    if win is None:
        return None
    lo, hi = win
    threads = (ctx.traffic["num_actor_cores"]
               * ctx.traffic["threads_per_actor_core"])
    puts = spans.clip(spans.spans(ctx.trace, SPAN), lo, hi)
    return 100.0 * sum(e - s for s, e in puts) / ((hi - lo) * threads)
