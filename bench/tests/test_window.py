"""The measured window's frame count (``drivers/sebulba.window``) on
hand-made marks: the frames acted between two marks, which are the frames
trained plus the change in the backlog (the frames acted but not yet
trained), and the bound on that backlog (``max_backlog``)."""

import json

import pytest

from bench import common
from bench.drivers.sebulba import Mark, max_backlog, window

PER_UPDATE = 1024  # 8 rows x 128 steps


def test_steady_backlog_gives_the_trained_count():
    # 3 trajectories queued or in the rings at both marks
    m0 = Mark(updates=10, t=100.0, acted=13 * PER_UPDATE + 40)
    m1 = Mark(updates=55, t=120.0, acted=58 * PER_UPDATE + 40)
    w = window(m0, m1, PER_UPDATE)
    assert w["trained_frames"] == 45 * PER_UPDATE
    assert w["backlog_frames"] == [3 * PER_UPDATE + 40] * 2
    assert w["train_frames_per_s"] == pytest.approx(45 * PER_UPDATE / 20.0)


def test_a_queue_that_drains_no_longer_inflates_the_count():
    # the actors act 40 trajectories' frames in the window; the learner
    # trains those and 4 that were queued at the first mark
    m0 = Mark(updates=10, t=100.0, acted=15 * PER_UPDATE)
    m1 = Mark(updates=54, t=120.0, acted=55 * PER_UPDATE)
    w = window(m0, m1, PER_UPDATE)
    assert w["trained_frames"] == 44 * PER_UPDATE
    assert w["backlog_frames"] == [5 * PER_UPDATE, 1 * PER_UPDATE]
    assert w["train_frames_per_s"] == pytest.approx(40 * PER_UPDATE / 20.0)


def test_the_probe_lets_the_program_go(tiny_cell, cpu_devices):
    """The reference runs once the program is freed: the probe, which
    outlives the program to hand the reference its captures, holds no
    reference to it."""
    import gc
    import weakref

    from bench.drivers import sebulba as drv

    cell = tiny_cell("lmrl-qwen2-copy64")
    s = drv.Session(cell, 7, cpu_devices(cell))
    s.probe.wait_for(drv.CAPTURED + 1, s.alive)
    s.measure(0.2)
    s.stop()
    program, probe = weakref.ref(s.seb), s.probe
    del s
    gc.collect()
    assert probe.p3 is not None
    assert program() is None


def test_the_backlog_bound_counts_queue_rings_and_the_learners_trajectory():
    traffic = json.loads((common.BENCH / "traffic" / "token-copy64-b8.json").read_text())
    # queue 4 + 2 actor threads' rings + 1 taken by the learner, 1,024 frames each
    assert max_backlog(traffic) == 7 * PER_UPDATE
    assert max_backlog(dict(traffic, threads_per_actor_core=3,
                            queue_capacity=2)) == 6 * PER_UPDATE


def test_dropped_trajectories_leave_the_backlog_behind():
    # a learner that drops every other trajectory: the actors' rate holds,
    # the trained count halves, and the backlog grows past any bound
    m0 = Mark(updates=10, t=100.0, acted=30 * PER_UPDATE)
    m1 = Mark(updates=30, t=120.0, acted=70 * PER_UPDATE)
    w = window(m0, m1, PER_UPDATE)
    assert w["train_frames_per_s"] == pytest.approx(40 * PER_UPDATE / 20.0)
    assert w["trained_frames"] == 20 * PER_UPDATE
    assert w["backlog_frames"] == [20 * PER_UPDATE, 40 * PER_UPDATE]
    traffic = json.loads((common.BENCH / "traffic" / "token-copy64-b8.json").read_text())
    assert max(w["backlog_frames"]) > max_backlog(traffic)
