"""Learner-pipeline tests (ISSUE 3): the donated, compile-cached update is
pinned bit-exact against the un-donated pre-cache path, compiles exactly
once per trajectory shape, accumulates metrics on device, and the
overlap-aware versioned publish never skips forever, never goes backwards,
and never hands an actor a torn or donated-away slot.  Where an actor core
is also a learner core the update keeps params and the publish hands the
slot the update's own output, with no copy."""

import json
import os
import queue
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def _donation(seb) -> dict:
    """One update on copies of ``seb``'s initial state -> whether it
    consumed params and opt_state, and whether it wrote each in place."""
    B, T = 6, 3
    params0, opt0 = seb.init(jax.random.key(0), (4,))
    traj = _make_traj(seb, B, T, 0)
    update, core = seb._get_update(traj)
    macc = seb._fresh_macc(jax.eval_shape(core, params0, opt0, traj)[2])

    p, o = _copy(params0), _copy(opt0)
    p_in, o_in = jax.tree.leaves(p), jax.tree.leaves(o)
    p_ptrs = [leaf.unsafe_buffer_pointer() for leaf in p_in]
    o_ptrs = [leaf.unsafe_buffer_pointer() for leaf in o_in]
    p2, o2, _ = update(p, o, traj, macc)
    out_ptrs = [leaf.unsafe_buffer_pointer()
                for leaf in jax.tree.leaves((p2, o2))]
    return {
        "params_consumed": all(leaf.is_deleted() for leaf in p_in),
        "params_live": not any(leaf.is_deleted() for leaf in p_in),
        "state_in_place": p_ptrs + o_ptrs == out_ptrs,
        "opt_consumed": all(leaf.is_deleted() for leaf in o_in),
        "opt_storage_reused": set(o_ptrs) <= set(out_ptrs),
    }


def _on_two_devices(check: str) -> dict:
    """``check(_make_seb())`` (a function of this module) in a subprocess
    that sees two CPU devices: one actor core and one learner core, no
    device shared."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    code = (
        f"import json, sys; sys.path[:0] = [{here!r}, {src!r}]; "
        "import jax, test_learner_pipeline as t; "
        "seb = t._make_seb(); "
        "assert len(jax.devices()) == 2 and not seb._shared_devices; "
        f"print(json.dumps(t.{check}(seb)))"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("topology", ["disjoint", "shared"])
def test_donated_update_runs_in_place(topology):
    """Donation must consume opt_state and reuse its storage (the learner
    state stops double-buffering).  Where no actor core is a learner core,
    params are donated too and the whole state is rewritten in place,
    leaf for leaf.  Where one is (the single CPU device), params stay live
    for the actors' slot; the update writes them to fresh storage or to
    the storage of an opt_state leaf of the same shape (XLA hands a
    donated buffer to the first output that matches it)."""
    if topology == "disjoint":
        out = _on_two_devices("_donation")
        assert out["params_consumed"], "donated params must be consumed"
        assert out["state_in_place"], "donation must reuse the state storage"
    else:
        seb = _make_seb()
        assert seb._shared_devices, "CPU test topology shares the device"
        out = _donation(seb)
        assert out["params_live"], "an actor may read params: keep them"
    assert out["opt_consumed"], "donated opt_state must be consumed"
    assert out["opt_storage_reused"], "donation must reuse opt_state's storage"


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


def _throttle(seb) -> dict:
    """Four publishes while the actor core has not consumed its slot, then
    one after it has -> whether each left the initial slot standing, the
    versions the slot showed, and the counters."""
    params0, _ = seb.init(jax.random.key(0), (4,))  # forced initial publish
    first = [seb.publishes_sent, seb.publishes_skipped]
    versions, stood = [seb._param_slots[0][0]], []
    slot0 = seb._param_slots[0][1]
    for _ in range(4):  # the learner outpaces the actor
        seb._publish_params(params0)
        versions.append(seb._param_slots[0][0])
        stood.append(seb._param_slots[0][1] is slot0)
    unconsumed = [seb.publishes_sent, seb.publishes_skipped,
                  seb._params_version]
    seb._slot_consumed[0] = seb._param_slots[0][0]  # actor picks the slot up
    seb._publish_params(params0)
    versions.append(seb._param_slots[0][0])
    return {
        "first": first, "stood": stood, "unconsumed": unconsumed,
        "picked_up": [seb.publishes_sent, seb.publishes_skipped],
        "last_stood": seb._param_slots[0][1] is slot0,
        "versions": versions, "aliased": seb.publishes_aliased,
    }


@pytest.mark.parametrize("topology", ["disjoint", "shared"])
def test_publish_skips_unconsumed_slot_and_stays_monotone(topology):
    """A slow actor core: publishes while its slot is unconsumed must be
    skipped (no transfer, slot untouched); once the actor stamps
    consumption the next publish lands with a strictly higher version.  A
    core that is also a learner core is never skipped: its publish moves
    no bytes, and a standing slot would keep an older params set alive
    beside the learner's."""
    if topology == "disjoint":
        out = _on_two_devices("_throttle")
        assert out["stood"] == [True] * 4, (
            "skipped publish must leave the slot standing"
        )
        # versions advance even when skipped
        assert out["unconsumed"] == [1, 4, 5]
        assert out["picked_up"] == [2, 4] and not out["last_stood"]
        assert out["aliased"] == 0
    else:
        out = _throttle(_make_seb())
        assert out["stood"] == [False] * 4
        assert out["unconsumed"] == [5, 0, 5]
        assert out["picked_up"] == [6, 0] and out["aliased"] == 6
    assert out["first"] == [1, 0]
    assert out["versions"][-1] == 6
    assert out["versions"] == sorted(out["versions"]), (
        "actor-visible versions must be monotone"
    )


def test_publish_throttle_off_publishes_every_update():
    seb = _make_seb(publish_throttle=False)
    params0, _ = seb.init(jax.random.key(0), (4,))
    for _ in range(5):
        seb._publish_params(params0)  # nobody consumes; all sent anyway
    assert seb.publishes_sent == 6 and seb.publishes_skipped == 0


def _leaves(tree):
    return [np.asarray(leaf).copy() for leaf in jax.tree.leaves(tree)]


def _assert_live_and_equal(tree, expected):
    leaves = jax.tree.leaves(tree)
    assert not any(leaf.is_deleted() for leaf in leaves), (
        "the update consumed buffers an actor slot holds"
    )
    for leaf, want in zip(leaves, expected):
        np.testing.assert_array_equal(np.asarray(leaf), want)


def test_publish_slot_survives_donated_update_on_shared_device():
    """Degenerate single-device topology: the update does not donate
    params, so a slot that aliases them stays live through it.  The
    publish hands the slot the update's own output buffers (same pointers:
    no copy), the next update leaves them live and unchanged, and
    ``publishes_aliased`` counts both slot writes."""
    seb = _make_seb()
    assert seb._shared_devices, "CPU test topology shares the device"
    params0, opt0 = seb.init(jax.random.key(0), (4,))
    _version, slot_params = seb._param_slots[0]
    slot_before = _leaves(slot_params)

    traj = _make_traj(seb, 6, 3, 0)
    update, core = seb._get_update(traj)
    macc = seb._fresh_macc(jax.eval_shape(core, params0, opt0, traj)[2])
    p2, o2, macc = update(params0, opt0, traj, macc)
    _assert_live_and_equal(slot_params, slot_before)

    seb._publish_params(p2)
    _version, slot_params = seb._param_slots[0]
    assert [leaf.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(slot_params)] == [
        leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(p2)
    ], "the slot must alias the update's output, not copy it"
    assert seb.publishes_sent == seb.publishes_aliased == 2

    slot_before = _leaves(slot_params)
    update(p2, o2, _make_traj(seb, 6, 3, 1), macc)
    _assert_live_and_equal(slot_params, slot_before)


def test_offpolicy_update_leaves_shared_slot_live():
    """The replay (off-policy) update follows the same rule: on a shared
    device it consumes the replay ring but not the params a slot holds."""
    from repro.configs.base import ReplayConfig

    seb = _make_seb(replay=ReplayConfig(capacity=12, sample_batch_size=6,
                                        min_size=6))
    assert seb._shared_devices, "CPU test topology shares the device"
    params0, opt0 = seb.init(jax.random.key(0), (4,))
    _version, slot_params = seb._param_slots[0]
    slot_before = _leaves(slot_params)

    traj = _make_traj(seb, 6, 3, 0)
    rstate = seb._replay.insert(seb._replay.init(traj), traj)
    update, core = seb._build_offpolicy_update(traj)
    key = jax.random.key(1)
    macc = seb._fresh_macc(jax.eval_shape(
        core, params0, opt0, rstate, traj, key, jnp.int32(0)
    )[3])
    ring = jax.tree.leaves(rstate)
    update(params0, opt0, rstate, _make_traj(seb, 6, 3, 1), macc, key,
           jnp.int32(0))
    assert all(leaf.is_deleted() for leaf in ring), "the ring is donated"
    _assert_live_and_equal(slot_params, slot_before)


def test_shared_device_learner_waits_for_each_update(monkeypatch):
    """On a shared device an update queued behind another would hold a
    fresh params set of its own, so after each publish the learner waits
    for the update it dispatched, on the params it published."""
    seb = _make_seb(batch=4, traj_len=2)
    assert seb._shared_devices, "CPU test topology shares the device"
    outputs, waited = [], []
    get_update = seb._get_update

    def recording_get_update(traj):
        update, core = get_update(traj)

        def run(*args):
            out = update(*args)
            outputs.append(out[0])
            return out

        return run, core

    block = jax.block_until_ready
    monkeypatch.setattr(seb, "_get_update", recording_get_update)
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda tree: waited.append(tree) or block(tree))
    out = seb.run(jax.random.key(0), (4,), total_frames=64)
    assert len(waited) == len(outputs) == out["updates"] > 0
    assert all(w is p for w, p in zip(waited, outputs))


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
