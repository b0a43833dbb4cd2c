"""The trace reduction, on hand-made traces with known answers and on a
small trace recorded on a TPU v5e chip (one LM-RL learner update and the
act steps around it, op names cut to their HLO instruction names)."""

import pathlib
import types

import pytest

from bench import common, devtrace
from bench.devtrace import Trace

DATA = pathlib.Path(__file__).parent / "data"


def sweep_union(intervals) -> int:
    """Busy time by an event sweep: an independent way to the union."""
    events = sorted([(s, 1) for s, _ in intervals] + [(e, -1) for _, e in intervals])
    total, depth, last = 0, 0, None
    for t, d in events:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (30, 31), (40, 41)]
    assert devtrace.union_ns(iv) == 15 + 10 + 1 + 1 == sweep_union(iv)
    assert devtrace.gaps_ns(iv, 0, 50) == [(15, 20), (31, 40), (41, 50)]
    assert devtrace.gaps_ns([], 3, 7) == [(3, 7)]


def toy() -> Trace:
    """Chip 0 acts; chips 1 and 2 learn, with an all-reduce inside each
    update; the host's learner thread covers the learners' idle gap."""
    ops = {
        0: [("%fusion.1 = f32[8] fusion(...)", 0, 400_000),
            ("%copy-done.2 = f32[8] copy-done(...)", 100_000, 300_000)],
        1: [("%convolution.3", 500_000, 700_000),
            ("%all-reduce.4 = f32[9] all-reduce(...)", 700_000, 750_000)],
        2: [("%convolution.3", 500_000, 690_000),
            ("%all-reduce-start.5", 690_000, 760_000)],
    }
    modules = {
        0: [("jit__device_act_step_fn(11)", 0, 400_000)],
        1: [("jit_update(12)", 500_000, 750_000)],
        2: [("jit_update(12)", 500_000, 760_000)],
    }
    host = [("python3", "PjitFunction(update)", 420_000, 480_000),
            ("python3", "queue wait", 400_000, 500_000),
            ("", "TraceMe", 0, 1_000_000)]
    return Trace(ops, modules, host)


def test_toy_readings():
    t = toy()
    assert (t.lo, t.hi, t.window_ns) == (0, 1_000_000, 1_000_000)
    assert t.busy_ns(0) == 400_000
    assert t.busy_ns(2) == 260_000
    assert t.module_time(1, r"^jit_update$") == (250_000, 1)
    assert t.module_time(0, r"_device_act_step_fn") == (400_000, 1)
    assert t.collective_time(1) == (50_000, 1)
    assert t.collective_time(2) == (70_000, 1)
    assert t.collective_time(0) == (0, 0)


def test_toy_breakdown():
    b = toy().breakdown([0, 1, 2])
    names = [n for n, _ in b["device_ops"]]
    # the async half is left out; the same op on two chips sums
    assert names[0] == "jit__device_act_step_fn/fusion.1"
    assert dict(b["device_ops"])["jit_update/convolution.3"] == pytest.approx(390e-6)
    assert "jit__device_act_step_fn/copy-done.2" not in names
    gaps = dict(b["idle_gaps"])
    # chip 0 idles 400-1000 us: the window-long TraceMe is the only event
    # covering at least half of it; the learners' 0-500 us gap is covered
    # best by the same; the 420-480 us PjitFunction covers too little
    assert gaps["TraceMe []"] == pytest.approx((600 + 500 + 250 + 500 + 240) * 1e-6)


def fake_ctx(trace, chips=1, learners=None, frames_per_s=2000.0):
    cell = common.Cell("lmrl-qwen2-copy64")
    ids = list(range(chips))
    return types.SimpleNamespace(
        cell=cell, cfg=cell.cfg, traffic=cell.traffic,
        cfg_module=cell.cfg_module, trace=trace,
        train_frames_per_s=frames_per_s, chips=chips, device_ids=ids,
        actor_ids=ids[:1], learner_ids=learners or ids,
        peaks=common.load_json(common.BENCH / "peaks.json")["TPU v5 lite"],
    )


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_json(str(DATA / "lmrl_one_update.json.gz"))


def test_recorded_busy_and_programs(recorded):
    t = recorded
    assert t.window_ns == 86_069_245
    ops = [(s, e) for _, s, e in t.ops[0]]
    assert t.busy_ns(0) == sweep_union(ops) == 85_718_780
    assert t.module_time(0, r"^jit_update$") == (74_119_957, 1)
    assert t.op_time(0, r"vtrace") == (2487, 1)
    assert t.collective_time(0) == (0, 0)


def test_recorded_breakdown(recorded):
    b = recorded.breakdown([0], top=4)
    assert [n for n, _ in b["device_ops"]] == [
        "jit_update/fusion.12", "jit_update/convolution_bitcast_fusion",
        "jit_update/fusion.1566", "jit_update/fusion.5546"]
    assert b["device_ops"][0][1] == pytest.approx(0.004939968)
    ops = [(s, e) for _, s, e in recorded.ops[0]]
    gaps = devtrace.gaps_ns(ops, recorded.lo, recorded.hi)
    assert sum(e - s for s, e in gaps) == 86_069_245 - 85_718_780
    long = sum(e - s for s, e in gaps if e - s >= devtrace.MIN_GAP_NS)
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(long / 1e9)


def test_recorded_metric_readers(recorded):
    ctx = fake_ctx(recorded)
    read = lambda m: ctx.cell.metric_reader(m).read(ctx)
    assert read("learner_update_ms") == pytest.approx(74.119957)
    assert read("actor_step_ms") == pytest.approx(8.421467 / 3)
    # 24.6 KB of V-trace I/O against 819 GB/s, over the kernel's 2.487 us
    assert read("vtrace_roofline") == pytest.approx(
        100 * 4 * (6 * 8 * 128 + 8) / 819e9 / 2.487e-6)
    roof = read("act_step_roofline")
    assert 0 < roof < 100
    assert read("idle_share.train") == pytest.approx(
        100 * (1 - 85_718_780 / 86_069_245))
    mfu = read("mfu.train")
    assert mfu == pytest.approx(
        100 * ctx.cfg_module.flops_per_frame(ctx.cfg, ctx.traffic) * 2000 / 197e12)


def test_readers_find_nothing_on_an_empty_trace():
    ctx = fake_ctx(Trace({0: []}, {0: []}, []))
    for m in ("actor_step_ms", "learner_update_ms", "act_step_roofline",
              "vtrace_roofline", "idle_share.train"):
        assert ctx.cell.metric_reader(m).read(ctx) is None, m
