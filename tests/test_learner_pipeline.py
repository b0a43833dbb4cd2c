"""Learner-pipeline tests (ISSUE 3): the donated, compile-cached update is
pinned bit-exact against the un-donated pre-cache path, compiles exactly
once per trajectory shape, accumulates metrics on device, and the
overlap-aware versioned publish never skips forever, never goes backwards,
and never hands an actor a torn or donated-away slot."""

import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.agents import BatchedMLPActorCritic
from repro.core.sebulba import Sebulba, SebulbaConfig
from repro.data.trajectory import Trajectory
from repro.envs import BatchedHostEnv, HostBandit


def _make_seb(batch=6, traj_len=3, **cfg):
    return Sebulba(
        env_factory=lambda seed: HostBandit(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        network=BatchedMLPActorCritic(4, hidden=(16,)),
        optimizer=optim.adam(1e-3),
        config=SebulbaConfig(
            num_actor_cores=1, actor_batch_size=batch,
            trajectory_length=traj_len, **cfg,
        ),
    )


def _make_traj(seb, batch, traj_len, seed):
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.RandomState(seed)
    sharding = NamedSharding(seb.learner_mesh, P("batch"))
    traj = Trajectory(
        obs=rng.rand(batch, traj_len, 4).astype(np.float32),
        actions=rng.randint(0, 4, (batch, traj_len)).astype(np.int32),
        rewards=rng.rand(batch, traj_len).astype(np.float32),
        discounts=np.full((batch, traj_len), 0.99, np.float32),
        behaviour_logp=np.log(
            rng.uniform(0.2, 0.9, (batch, traj_len))
        ).astype(np.float32),
        bootstrap_obs=rng.rand(batch, 4).astype(np.float32),
    )
    return jax.tree.map(lambda x: jax.device_put(x, sharding), traj)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


# ------------------------------------------------- donated update semantics


def test_donated_cached_update_bit_exact_vs_precache_path():
    """The ISSUE 3 pin: N updates through the donated, compile-cached,
    accumulator-carrying path must reproduce the pre-cache reference (the
    same shard_map'd core jitted with NO donation) bit-for-bit — params,
    opt_state, and the metric means."""
    B, T, N = 6, 3, 4
    seb = _make_seb(B, T)
    params0, opt0 = seb.init(jax.random.key(0), (4,))
    trajs = [_make_traj(seb, B, T, 10 + i) for i in range(N)]

    # reference: the pre-PR program — identical math, no donation, fresh
    # metrics returned per update, averaged on host
    reference = jax.jit(seb._build_update(trajs[0]))
    p_ref, o_ref = params0, opt0
    ms = []
    for traj in trajs:
        p_ref, o_ref, m = reference(p_ref, o_ref, traj)
        ms.append(m)
    ref_means = {k: float(np.mean([float(m[k]) for m in ms])) for k in ms[0]}

    update, core = seb._get_update(trajs[0])
    macc = seb._fresh_macc(jax.eval_shape(core, params0, opt0, trajs[0])[2])
    p, o = _copy(params0), _copy(opt0)
    for traj in trajs:
        p, o, macc = update(p, o, _copy(traj), macc)

    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(o_ref), jax.tree.leaves(o)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    drained = seb._drain_macc(macc)
    assert set(drained) == set(ref_means)
    for k in ref_means:
        np.testing.assert_allclose(drained[k], ref_means[k], rtol=1e-6)


def test_donated_update_runs_in_place():
    """Donation must consume params/opt_state and reuse their storage (the
    learner state stops double-buffering)."""
    B, T = 6, 3
    seb = _make_seb(B, T)
    params0, opt0 = seb.init(jax.random.key(0), (4,))
    traj = _make_traj(seb, B, T, 0)
    update, core = seb._get_update(traj)
    macc = seb._fresh_macc(jax.eval_shape(core, params0, opt0, traj)[2])

    p, o = _copy(params0), _copy(opt0)
    in_ptrs = [l.unsafe_buffer_pointer() for l in jax.tree.leaves((p, o))]
    old_leaf = jax.tree.leaves(p)[0]
    p2, o2, _ = update(p, o, traj, macc)
    assert old_leaf.is_deleted(), "donated params must be consumed"
    out_ptrs = [l.unsafe_buffer_pointer() for l in jax.tree.leaves((p2, o2))]
    assert in_ptrs == out_ptrs, "donation must reuse the state storage"


def test_one_compile_per_trajectory_shape():
    """The compile-count probe: N same-shape updates -> exactly one trace;
    a second trajectory shape -> exactly one more."""
    B, T = 6, 3
    seb = _make_seb(B, T)
    params0, opt0 = seb.init(jax.random.key(0), (4,))
    traj = _make_traj(seb, B, T, 0)
    update, core = seb._get_update(traj)
    macc = seb._fresh_macc(jax.eval_shape(core, params0, opt0, traj)[2])
    assert seb.update_traces == 0
    p, o = _copy(params0), _copy(opt0)
    for i in range(4):
        p, o, macc = update(p, o, _make_traj(seb, B, T, i), macc)
        update2, _ = seb._get_update(_make_traj(seb, B, T, 99))
        assert update2 is update, "same shape must hit the update cache"
    assert seb.update_traces == 1, seb.update_traces

    # a new trajectory shape (different T) builds+compiles exactly once more
    traj_t2 = _make_traj(seb, B, T + 1, 0)
    update_b, _ = seb._get_update(traj_t2)
    assert update_b is not update
    p2, o2 = _copy(p), _copy(o)
    p2, o2, _ = update_b(p2, o2, traj_t2, seb._fresh_macc())
    assert seb.update_traces == 2, seb.update_traces


def test_metrics_accumulator_drains_means_and_resets():
    seb = _make_seb()
    params0, opt0 = seb.init(jax.random.key(0), (4,))
    traj = _make_traj(seb, 6, 3, 0)
    update, core = seb._get_update(traj)
    macc = seb._fresh_macc(jax.eval_shape(core, params0, opt0, traj)[2])
    assert seb._drain_macc(macc) is None  # empty accumulator -> no metrics
    p, o = _copy(params0), _copy(opt0)
    p, o, macc = update(p, o, traj, macc)
    m = seb._drain_macc(macc)
    assert m is not None and np.isfinite(m["loss"])
    assert seb._drain_macc(seb._fresh_macc()) is None  # reset drains empty


# ------------------------------------------------ overlap-aware publishing


def test_publish_skips_unconsumed_slot_and_stays_monotone():
    """A slow actor core: publishes while its slot is unconsumed must be
    skipped (no transfer, slot untouched); once the actor stamps
    consumption the next publish lands with a strictly higher version."""
    seb = _make_seb()
    params0, _ = seb.init(jax.random.key(0), (4,))  # forced initial publish
    assert seb.publishes_sent == 1 and seb.publishes_skipped == 0
    v0, slot0 = seb._param_slots[0]

    observed = [v0]
    for _ in range(4):  # learner outpaces the actor: all skipped
        seb._publish_params(params0)
        version, slot = seb._param_slots[0]
        observed.append(version)
        assert slot is slot0, "skipped publish must leave the slot standing"
    assert seb.publishes_sent == 1 and seb.publishes_skipped == 4
    assert seb._params_version == 5  # versions advance even when skipped

    seb._slot_consumed[0] = seb._param_slots[0][0]  # actor picks the slot up
    seb._publish_params(params0)
    version, slot = seb._param_slots[0]
    observed.append(version)
    assert slot is not slot0 and version == 6
    assert seb.publishes_sent == 2
    assert observed == sorted(observed), "actor-visible versions must be monotone"


def test_publish_throttle_off_publishes_every_update():
    seb = _make_seb(publish_throttle=False)
    params0, _ = seb.init(jax.random.key(0), (4,))
    for _ in range(5):
        seb._publish_params(params0)  # nobody consumes; all sent anyway
    assert seb.publishes_sent == 6 and seb.publishes_skipped == 0


def test_publish_slot_survives_donated_update_on_shared_device():
    """Degenerate single-device topology: the published slot must own its
    storage, so the donated learner update consuming params cannot
    invalidate what actor threads are reading (device_put to the same
    device aliases — the publish must copy)."""
    seb = _make_seb()
    assert seb._shared_devices, "CPU test topology shares the device"
    params0, opt0 = seb.init(jax.random.key(0), (4,))
    _version, slot_params = seb._param_slots[0]
    slot_before = np.asarray(jax.tree.leaves(slot_params)[0]).copy()

    traj = _make_traj(seb, 6, 3, 0)
    update, core = seb._get_update(traj)
    macc = seb._fresh_macc(jax.eval_shape(core, params0, opt0, traj)[2])
    update(params0, opt0, traj, macc)  # donates params0/opt0

    leaf = jax.tree.leaves(slot_params)[0]
    assert not leaf.is_deleted(), "slot must not alias donated learner state"
    np.testing.assert_array_equal(np.asarray(leaf), slot_before)


# ------------------------------------------- actor-side queue put (retry)


def _handle(seb, slot=0):
    """A bare ActorHandle for exercising ``_queue_put`` outside ``run``
    (matches what the supervisor would hand an actor incarnation)."""
    from repro.core.supervision import ActorHandle

    return ActorHandle(slot=slot, incarnation=0, core_id=0, seed=slot + 1)


def test_queue_put_retries_on_full_and_counts_blocked():
    """Satellite: a full queue must block-and-retry (counting the blocked
    intervals on the incarnation's handle), not silently drop the
    trajectory."""
    seb = _make_seb(queue_capacity=1)
    seb._queue.put("occupying")  # fill the queue
    handle = _handle(seb)
    done = threading.Event()
    result = {}

    def put():
        result["ok"] = seb._queue_put("shards", handle, 3)
        done.set()

    t = threading.Thread(target=put, daemon=True)
    t.start()
    assert not done.wait(timeout=1.2), "put must still be retrying"
    assert handle.put_blocked >= 1
    assert seb._queue.get() == "occupying"  # learner frees a slot
    assert done.wait(timeout=5.0)
    # puts are tagged with the membership epoch at put time (multi-host
    # elasticity: the learner drops trajectories that straddle a reshard),
    # the params version the trajectory acted with (the policy lag) and
    # the trajectory's id: slot 0, incarnation 0, its first put
    assert result["ok"] and seb._queue.get() == (seb._epoch, 3, "0r0:0",
                                                 "shards")
    assert handle.traj_dropped == 0
    assert handle.first_put_at is not None  # recovery-latency stamp landed


def test_queue_put_drops_only_on_stop():
    seb = _make_seb(queue_capacity=1)
    seb._queue.put("occupying")
    seb._stop.set()
    handle = _handle(seb)
    assert seb._queue_put("shards", handle, 3) is False
    assert handle.traj_dropped == 1


def test_queue_put_unblocks_on_watchdog_cancel():
    """Satellite (graceful shutdown): every put retry must re-check not
    just the global stop event but this incarnation's cancel flag — a
    watchdog-abandoned actor must never spin in the retry loop."""
    seb = _make_seb(queue_capacity=1)
    seb._queue.put("occupying")
    handle = _handle(seb)
    done = threading.Event()
    result = {}

    def put():
        result["ok"] = seb._queue_put("shards", handle, 3)
        done.set()

    t = threading.Thread(target=put, daemon=True)
    t.start()
    assert not done.wait(timeout=0.8), "put must still be retrying"
    handle.cancel.set()  # watchdog abandons the incarnation
    assert done.wait(timeout=5.0), "cancel must break the retry loop"
    assert result["ok"] is False and handle.traj_dropped == 1


def test_run_reports_publish_and_queue_counters():
    seb = _make_seb(batch=4, traj_len=2, threads_per_actor_core=2)
    out = seb.run(jax.random.key(0), (4,), total_frames=200)
    assert out["updates"] > 0
    assert out["param_version"] == out["updates"] + 1
    assert out["publishes_sent"] + out["publishes_skipped"] == (
        out["param_version"]
    )
    for key in ("put_blocked", "traj_dropped"):
        assert key in out and out[key] >= 0
    assert np.isfinite(out["metrics"]["loss"])
