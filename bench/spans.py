"""The program's own host spans in a reduced trace.

``repro.core.sebulba`` opens a ``jax.profiler.TraceAnnotation`` around
each queue put and get, each update's dispatch and each parameter
publish (``sebulba.actor.put``, ``sebulba.learner.get``,
``sebulba.learner.update``, ``sebulba.learner.publish``, ...).  They land
in the same capture as the device's operations, on its clock, and the
reduction keeps them among the host events: (thread, name, start, end).
Every reader built on them returns None where the trace holds no
``sebulba.*`` span, as a trace of a program without them does.

The profiler records a span only if it opened and closed while the
capture ran, so a wait that straddles the capture's start or end is
missing.  The learner's spans follow one another with no more than
bookkeeping between them, so from the start of its first span to the end
of its last (``learner_window``) every wait of the learner was seen.
"""

from __future__ import annotations

PREFIX = "sebulba."
LEARNER = "sebulba.learner."


def has_spans(trace) -> bool:
    return any(name.startswith(PREFIX) for _, name, _, _ in trace.host)


def spans(trace, name: str) -> list[tuple[int, int]]:
    """[start, end) of every host span called ``name``, by start."""
    return sorted((s, e) for _, n, s, e in trace.host if n == name)


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of ``intervals`` inside [lo, hi)."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def merge(intervals) -> list[tuple[int, int]]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_ns(a, b) -> int:
    """Length of the intersection of the unions of ``a`` and ``b``."""
    a, b = merge(a), merge(b)
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def learner_window(trace) -> tuple[int, int] | None:
    """From the start of the learner's first span to the end of its last,
    inside the trace's window; None without learner spans."""
    ls = [(s, e) for _, n, s, e in trace.host if n.startswith(LEARNER)]
    if not ls:
        return None
    lo = max(trace.lo, min(s for s, _ in ls))
    hi = min(trace.hi, max(e for _, e in ls))
    return (lo, hi) if hi > lo else None
