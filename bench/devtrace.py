"""Device traces: capture one, and reduce it to what the per-layer metrics
read.

A capture is the JAX profiler over a short steady window, with the Python
tracer off (it would record every Python call of every actor thread).
The reduction keeps, per device, the intervals of its operations ("XLA
Ops") and of its programs ("XLA Modules"), and the host's events, all on
the trace's own clock, and answers four questions: how long each device
was busy (the union of its operations), how long each named program ran,
how long the collectives ran, and where the time and the idle gaps went.

``Trace.to_json`` / ``Trace.from_json`` keep a reduced trace as a small
file, which is how the tests check the reduction against a recorded one.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
)
# the halves of an asynchronous copy or slice span the work they overlap:
# they count towards busy time, not among the operations that took it
ASYNC_HALF = re.compile(r"^%?[\w.-]*-(start|done)(\.\d+)?( = |$)")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a gap shorter than this is the device's own scheduling, not the host's
MIN_GAP_NS = 10_000


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def _module_base(name: str) -> str:
    """'jit_update(123)' -> 'jit_update'."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def union_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int):
    """The idle [start, end) gaps between the union of ``intervals`` and
    the window [lo, hi)."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """ops/modules: {device id: [(name, start_ns, end_ns)]};
    host: [(thread, name, start_ns, end_ns)]."""

    def __init__(self, ops: dict, modules: dict, host: list):
        self.ops = {int(k): v for k, v in ops.items()}
        self.modules = {int(k): v for k, v in modules.items()}
        self.host = host
        starts = [s for v in self.ops.values() for _, s, _ in v]
        ends = [e for v in self.ops.values() for _, _, e in v]
        starts += [s for _, _, s, _ in host]
        ends += [e for _, _, _, e in host]
        self.lo = min(starts) if starts else 0
        self.hi = max(ends) if ends else 0

    # ------------------------------------------------------------- loading

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        from jax.profiler import ProfileData

        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError(f"no .xplane.pb under {log_dir}")
        data = ProfileData.from_file(paths[0])
        ops, modules, host = {}, {}, []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                dev = int(m.group(1))
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        dst = ops.setdefault(dev, [])
                    elif line.name == "XLA Modules":
                        dst = modules.setdefault(dev, [])
                    else:
                        continue
                    for e in line.events:
                        s = int(e.start_ns)
                        dst.append((e.name, s, s + int(e.duration_ns)))
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for e in line.events:
                        if e.duration_ns > 0:
                            s = int(e.start_ns)
                            host.append(
                                (line.name, e.name, s, s + int(e.duration_ns))
                            )
        return cls(ops, modules, host)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            d = json.load(f)
        return cls(d["ops"], d["modules"],
                   [tuple(h) for h in d["host"]])

    def to_json(self, path: str, lo: int | None = None,
                hi: int | None = None) -> None:
        """Write the trace, or the part of it inside [lo, hi), to a
        (gzipped, where the name ends in .gz) JSON file."""
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        keep = lambda v: [list(x) for x in v if x[-2] >= lo and x[-1] <= hi]
        d = {
            "ops": {k: keep(v) for k, v in self.ops.items()},
            "modules": {k: keep(v) for k, v in self.modules.items()},
            "host": keep(self.host),
        }
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump(d, f)

    # ------------------------------------------------------------ readings

    @property
    def window_ns(self) -> int:
        return self.hi - self.lo

    def busy_ns(self, dev: int) -> int:
        return union_ns((s, e) for _, s, e in self.ops.get(dev, []))

    def module_time(self, dev: int, pattern: str) -> tuple[int, int]:
        """(total ns, calls) of the programs on ``dev`` whose name matches
        ``pattern``."""
        rx = re.compile(pattern)
        hits = [(s, e) for n, s, e in self.modules.get(dev, [])
                if rx.search(_module_base(n))]
        return sum(e - s for s, e in hits), len(hits)

    def op_time(self, dev: int, pattern) -> tuple[int, int]:
        """(union ns, events) of the operations on ``dev`` whose name
        matches ``pattern``."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        hits = [(s, e) for n, s, e in self.ops.get(dev, []) if rx.search(n)]
        return union_ns(hits), len(hits)

    def collective_time(self, dev: int) -> tuple[int, int]:
        return self.op_time(dev, COLLECTIVE)

    # ------------------------------------------------------------ breakdown

    def breakdown(self, devs, top: int = 10) -> dict:
        """The device operations that took most time, summed over
        ``devs`` (the halves of asynchronous copies left out), and the
        idle time of ``devs`` grouped by the host event that covered each
        gap best."""
        per_op: dict[str, int] = {}
        for d in devs:
            mods = sorted((s, e, _module_base(n))
                          for n, s, e in self.modules.get(d, []))
            starts = [m[0] for m in mods]
            for n, s, e in self.ops.get(d, []):
                if ASYNC_HALF.search(n):
                    continue
                name = op_label(n, mods, starts, s)
                per_op[name] = per_op.get(name, 0) + (e - s)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

        host = HostIndex(self.host)
        per_gap: dict[str, int] = {}
        for d in devs:
            busy = [(s, e) for _, s, e in self.ops.get(d, [])]
            for gs, ge in gaps_ns(busy, self.lo, self.hi):
                if ge - gs < MIN_GAP_NS:
                    continue
                label = _host_label(host, gs, ge)
                per_gap[label] = per_gap.get(label, 0) + (ge - gs)
        gaps = sorted(per_gap.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, t / 1e9] for n, t in gaps],
        }


def op_label(op: str, mods, starts, start: int) -> str:
    """'%fusion.12 = (...) fusion(...)' inside program 'jit_update(7)' ->
    'jit_update/fusion.12'."""
    short = op.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(starts, start) - 1
    if i >= 0 and mods[i][0] <= start < mods[i][1]:
        return f"{mods[i][2]}/{short}"
    return short


def _host_label(host, gs: int, ge: int) -> str:
    """The host event that overlaps the gap [gs, ge) most; among those
    covering at least half of it, the shortest (the most specific).
    ``host`` is a ``HostIndex``."""
    best, best_ov, cands = None, 0, []
    for thread, name, s, e in host.overlapping(gs, ge):
        ov = min(e, ge) - max(s, gs)
        if ov > best_ov:
            best, best_ov = (thread, name), ov
        if 2 * ov >= ge - gs:
            cands.append((e - s, thread, name))
    if cands:
        _, thread, name = min(cands)
        return f"{name} [{thread}]"
    if best is not None:
        return f"{best[1]} [{best[0]}]"
    return "no host event"


class HostIndex:
    """Host events indexed for overlap queries: the long ones are few and
    checked every time; the short ones are found by their start."""

    LONG_NS = 10_000_000

    def __init__(self, host):
        self.long = [h for h in host if h[3] - h[2] > self.LONG_NS]
        self.short = sorted(
            (h for h in host if h[3] - h[2] <= self.LONG_NS),
            key=lambda h: h[2],
        )
        self.starts = [h[2] for h in self.short]

    def overlapping(self, lo: int, hi: int):
        i = bisect.bisect_left(self.starts, lo - self.LONG_NS)
        j = bisect.bisect_left(self.starts, hi)
        for h in self.short[i:j]:
            if h[3] > lo:
                yield h
        for h in self.long:
            if h[2] < hi and h[3] > lo:
                yield h
