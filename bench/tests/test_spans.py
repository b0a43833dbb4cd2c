"""The readers of the program's own spans (``bench/spans.py`` and the four
metrics that use it), on hand-made traces with known answers, on a trace
of the LM-RL cell recorded on a TPU v5e chip (cut to one learner update
and the act steps and spans around it), and on traces with no
``sebulba.*`` span, as the program had before it opened any."""

import pathlib
import types

import pytest

from bench import common, devtrace, spans
from bench.devtrace import Trace

DATA = pathlib.Path(__file__).parent / "data"
READERS = ("publish_ms", "publish_idle_share", "learner_wait_share",
           "actor_put_wait_share")
US = 1_000  # ns


def ctx_for(trace, chips=1):
    cell = common.Cell("lmrl-qwen2-copy64")
    ids = list(range(chips))
    return types.SimpleNamespace(
        cell=cell, cfg=cell.cfg, traffic=cell.traffic,
        cfg_module=cell.cfg_module, trace=trace,
        train_frames_per_s=2000.0, chips=chips, device_ids=ids,
        actor_ids=ids[:1], learner_ids=ids,
        peaks=common.load_json(common.BENCH / "peaks.json")["TPU v5 lite"],
    )


def read(ctx, metric):
    return ctx.cell.metric_reader(metric).read(ctx)


def handmade() -> Trace:
    """Chip 0 idles 300-400 us and 900-950 us of a 1,000 us window; chip 1
    never idles.  The learner waits, updates and publishes from 20 us to
    960 us; the first publish is open over 50 us of chip 0's first gap.
    Two actor threads put: the first put opens before the learner's first
    span and the last closes after its last."""
    ops = {
        0: [("%fusion.1", 0, 300 * US), ("%fusion.2", 400 * US, 900 * US),
            ("%fusion.3", 950 * US, 1000 * US)],
        1: [("%fusion.4", 0, 1000 * US)],
    }
    learner = [
        ("sebulba.learner.get", 20, 250), ("sebulba.learner.update", 250, 280),
        ("sebulba.learner.publish", 280, 350), ("sebulba.learner.get", 350, 600),
        ("sebulba.learner.update", 600, 640),
        ("sebulba.learner.publish", 640, 700), ("sebulba.learner.log", 700, 710),
        ("sebulba.learner.get", 710, 960),
    ]
    actors = [("sebulba.actor.put", 10, 30), ("sebulba.actor.put", 200, 330),
              ("sebulba.actor.put", 500, 520), ("sebulba.actor.put", 900, 990)]
    host = [("python3", n, s * US, e * US) for n, s, e in learner + actors]
    host.append(("python3", "PjitFunction(copy)", 290 * US, 300 * US))
    return Trace(ops, {0: [], 1: []}, host)


def test_interval_helpers():
    iv = [(5, 10), (0, 3), (8, 12), (20, 30)]
    assert spans.merge(iv) == [(0, 3), (5, 12), (20, 30)]
    assert spans.clip(iv, 2, 25) == [(5, 10), (2, 3), (8, 12), (20, 25)]
    assert spans.overlap_ns(iv, [(2, 9), (11, 21), (29, 40)]) == 1 + 4 + 1 + 1 + 1
    assert spans.overlap_ns(iv, []) == 0


def test_handmade_readings():
    t = handmade()
    assert (t.lo, t.hi) == (0, 1000 * US)
    assert spans.learner_window(t) == (20 * US, 960 * US)
    ctx = ctx_for(t, chips=2)
    # the two publishes: 70 us and 60 us
    assert read(ctx, "publish_ms") == pytest.approx(0.065)
    # chip 0 idles 50 us under the first publish, chip 1 never
    assert read(ctx, "publish_idle_share") == pytest.approx(100 * 25 / 1000)
    assert read(ctx_for(t), "publish_idle_share") == pytest.approx(5.0)
    assert read(ctx_for(t), "publish_idle_share") <= read(
        ctx_for(t), "idle_share.train")
    # 230 + 250 + 250 us of waits in the learner's 940 us
    assert read(ctx, "learner_wait_share") == pytest.approx(100 * 730 / 940)
    # puts clipped to the learner's window: 10 + 130 + 20 + 60 us, over
    # 940 us times the traffic's two actor threads
    assert ctx.traffic["num_actor_cores"] * ctx.traffic[
        "threads_per_actor_core"] == 2
    assert read(ctx, "actor_put_wait_share") == pytest.approx(
        100 * 220 / (940 * 2))


@pytest.fixture(scope="module")
def recorded():
    """One learner update of ``lmrl-qwen2-copy64`` (seed 2718281828): from
    the start of the learner's wait to the end of its publish, 279.7 ms,
    times from the cut's start; host events kept are the program's spans
    and the runtime's ``PjitFunction`` dispatches."""
    return Trace.from_json(str(DATA / "lmrl_spans.json.gz"))


def test_recorded_spans(recorded):
    t = recorded
    assert (t.lo, t.hi) == (0, 279_681_311)
    assert spans.spans(t, "sebulba.learner.get") == [(0, 208_780)]
    assert spans.spans(t, "sebulba.learner.update") == [(214_400, 14_210_920)]
    assert spans.spans(t, "sebulba.learner.publish") == [
        (14_220_829, 279_681_311)]
    assert spans.spans(t, "sebulba.actor.put") == [
        (159_529_105, 159_541_585)]
    assert spans.learner_window(t) == (0, 279_681_311)
    # the renamed kernel is still what vtrace_roofline finds
    assert [n for n, _, _ in t.ops[0] if "vtrace" in n] == ["%vtrace.1"]


def test_recorded_readings(recorded):
    t = recorded
    ctx = ctx_for(t)
    w = t.window_ns
    assert read(ctx, "publish_ms") == pytest.approx(265.460482)
    # idle under the publish, gap by gap: an independent way to the number
    busy = [(s, e) for _, s, e in t.ops[0]]
    lo, hi = 14_220_829, 279_681_311
    idle = sum(max(0, min(e, hi) - max(s, lo))
               for s, e in devtrace.gaps_ns(busy, t.lo, t.hi))
    assert idle == 1_410_457
    assert read(ctx, "publish_idle_share") == pytest.approx(100 * idle / w)
    assert read(ctx, "publish_idle_share") < read(ctx, "idle_share.train")
    assert read(ctx, "learner_wait_share") == pytest.approx(
        100 * 208_780 / w)
    assert read(ctx, "actor_put_wait_share") == pytest.approx(
        100 * 12_480 / (2 * w))
    # the benchmark's earlier readers still read this cut of the change
    assert read(ctx, "learner_update_ms") == pytest.approx(74.119417)
    assert read(ctx, "vtrace_roofline") is not None


def test_readers_find_nothing_without_program_spans():
    empty = Trace({0: []}, {0: []}, [])
    # a trace of a program that opens no span: device ops and the
    # runtime's own host events only
    bare = Trace({0: [("%fusion.1", 0, 100)]}, {0: []},
                 [("python3", "PjitFunction(update)", 10, 20)])
    for t in (empty, bare):
        assert not spans.has_spans(t)
        for m in READERS:
            assert read(ctx_for(t), m) is None, m
