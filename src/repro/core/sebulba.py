"""Sebulba — decomposed actors and learners on a single host (paper Fig. 3).

Reproduces the paper's dataflow exactly:

  * the host's devices are split into A actor cores + (n-A) learner cores;
  * one-or-more Python threads per actor core each own a *batched host
    environment* (repro/envs/batched_env.py) and alternate in using their
    actor core, hiding env-stepping latency behind device inference;
  * the actor hot path is ONE fused donated-jit ``act_step`` per env step:
    RNG split -> policy inference -> log-prob -> in-place write into a
    preallocated device-resident ``DeviceTrajectoryBuffer``
    (repro/data/trajectory.py), with the per-step host data (rewards,
    discounts) batched into a single (2, B) transfer.  The only host sync
    per step is reading the actions the env needs;
  * when the ring is full the actor drains it (the trajectory leaves alias
    the donated ring storage — no stacking, no copy), slices the batch on
    the actor core, and sends each shard *device-to-device* to its learner
    core — trajectory leaves never round-trip through host numpy;
  * a single learner thread assembles the shards into one globally-sharded
    batch over the learner mesh and runs the same update on every learner
    core (shard_map), averaging gradients with jax.lax.pmean;
  * the learner update is built once per trajectory shape and cached, with
    opt_state, the incoming trajectory shards, and the on-device metrics
    accumulator donated — the steady-state learner loop is one XLA
    dispatch per update that reuses its buffers in place and never syncs
    device->host (metrics drain to host only on ``log_every``
    boundaries).  Params are donated only where no actor reads them: when
    every actor core is off the learner mesh;
  * after each update the learner publishes fresh parameters
    device-to-device to every actor core through a lock-free versioned
    params slot (device_put dispatches async, so the publish never blocks
    the learner); actor threads pick the slot up before their next step.
    An actor core that is also a learner core gets a handle on the
    update's own (undonated) output: no copy, no device program, no skip;
    there the learner lets each update finish before it dispatches the
    next, since a queued update would hold a params set of its own.  The
    publish is overlap-aware: a core that has not consumed its last
    publish is skipped (``SebulbaConfig.publish_throttle``), so params
    bytes only move when an actor will actually act on them.

The V-trace (IMPALA) objective corrects for the actor/learner policy lag.
``learner_microbatches`` implements the paper's MuZero trick of splitting
the learner batch into N sequential micro-updates to decouple acting batch
size from learning batch size.

Off-policy mode (``SebulbaConfig.replay``): the paper's MuZero recipe keeps
a replay buffer between actors and learner.  Actor trajectory shards are
written into a device-resident replay ring sharded across the learner mesh
(repro/replay/), and each learner update trains on a *mixed* batch — the
fresh online shard concatenated with trajectories sampled from replay —
inside one fused ``shard_map`` step: insert -> sample -> weighted V-trace
update -> priority write-back, with the ring buffers donated so nothing
round-trips through the host.

Agents plug in through the canonical ``repro.api`` protocol — ``init`` /
``initial_carry`` / ``act(params, obs, rng, carry)`` / ``loss(params,
traj, weights)`` with capabilities DECLARED on an ``AgentSpec``
(``recurrent``, ``replay``, ``extras_keys``) and validated once at
construction (``api.resolve_agent``), never sniffed from signatures at
runtime.  Recurrent agents (R2D2, repro/agents/recurrent.py) get their
carry threaded through the fused act-step (donated, reset on episode
boundaries via the discount channel), the carry entering step 0 of each
trajectory slice stored alongside it (``Trajectory.init_carry`` — the
R2D2 "stored state", which rides the replay ring like any other leaf), and
a learner-side burn-in (``SebulbaConfig.burn_in``) that re-unrolls the
first K steps gradient-free to refresh the stale stored state before the
V-trace loss.  Feed-forward agents declare no capabilities and thread the
empty () carry — zero extra leaves, bit-identical programs.  The protocol
costs the hot path nothing: the NamedTuple auxes flatten to the same
leaves, so every donated jit traces to the pre-protocol program.  See
ARCHITECTURE.md §Protocol.

Fault tolerance (repro/core/supervision.py, repro/fault/): actor threads
run as supervised slots — crash -> exponential-backoff restart under a
fresh RNG fold, repeat offender -> quarantine with the surviving actors
still feeding every learner shard, hang -> heartbeat-watchdog cancel —
and a learner that raises a structured ``SebulbaStallError`` (full
diagnostics + every traceback) when no actor can make progress, instead
of polling an empty queue forever.  Checkpoints are atomic + checksummed
with newest-valid-stamp fallback and ``fit(..., auto_resume=True)``.
The supervision hot-path cost is one monotonic heartbeat stamp per env
step.  See ARCHITECTURE.md §Fault tolerance & elasticity.

Multi-host elasticity (repro/distributed/): mounting a ``HostSupervisor``
as ``cluster=`` makes this Sebulba one host of an elastic fleet.  The
learner loop polls host membership once per drain iteration; a
membership epoch bump (a host's lease expired, or a host rejoined)
forces a param republish so every actor restarts from a consistent
version, trajectories are epoch-tagged at enqueue and stale-tagged ones
are dropped at the learner (the epoch-checked insert path — a trajectory
routed under a dead membership never crosses the bump), and the result
schema reports ``hosts_joined`` / ``hosts_lost`` / ``reshards`` /
``epoch``.  See ARCHITECTURE.md §Multi-host elasticity.

Tracing: the runner opens ``jax.profiler.TraceAnnotation`` spans at its
queue, update and publish, always; while no profiler runs a span records
nothing and costs only its Python call.  Capture them with the device's
ops, on one clock, by running ``fit`` under ``jax.profiler.trace(log_dir)``
(or between ``jax.profiler.start_trace`` and ``stop_trace``) and open the
capture in TensorBoard's or Perfetto's trace viewer; a device idle gap
then lines up with the span the host had open.  A span is recorded only if
it opens and closes while the capture runs.  Every Python thread shows on
a host line named after the interpreter, so the role is in the span's
name:

  sebulba.actor.put         an actor thread handing a trajectory to the
                            learner queue, full-queue retries included;
                            stats ``traj`` (the trajectory's id,
                            ``<slot>r<incarnation>:<sequence>``) and
                            ``version`` (the params version its first step
                            acted with)
  sebulba.learner.get       each wait on the queue (timeouts included)
  sebulba.learner.update    the update's dispatch; stats ``traj``,
                            ``version`` and ``lag`` (with replay, of the
                            trajectory the update inserts; the batch it
                            trains on is sampled from the buffer)
  sebulba.learner.publish   the parameter publish; stats ``version``,
                            ``sent``, ``skipped`` and ``aliased`` (actor
                            cores; ``aliased``: sent to a learner core,
                            as a handle on the update's output)
  sebulba.learner.checkpoint  a checkpoint write
  sebulba.learner.log       the ``log_every`` metrics drain and print

One trajectory's put and update share its ``traj``.  The policy lag of an
update is the number of params versions published between the one its
trajectory's first step acted with and the one the update starts from;
the result reports its mean and maximum (``policy_lag_mean``,
``policy_lag_max``) and the ``log_every`` line prints them.  The queue's
capacity, the actor threads and the publish throttle set it; V-trace
corrects for it.  The programs keep stable names for the trace's
reduction: the act step ``jit__device_act_step_fn`` (``jit__act_step_fn``
with host envs), the update ``jit_update``; named scopes mark the act
step's decode layers, unembedding, sampling, ring write and env step, and
the update's ``forward_backward`` (inside it JAX's ``jvp(...)`` and
``transpose(jvp(...))`` prefixes split the two passes), V-trace and
optimizer.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import warnings
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import api, optim
# ImpalaAgent moved to repro/agents/impala.py with the repro.api redesign;
# re-exported here for back-compat with pre-protocol imports.
from repro.agents.impala import ImpalaAgent  # noqa: F401
from repro.configs.base import ReplayConfig
from repro.core.supervision import (
    ActorHandle,
    ActorSupervisor,
    SebulbaStallError,  # noqa: F401  (re-exported: the learner raises it)
)
from repro.core.topology import CoreSplit, split_devices
from repro.envs.device_env import DeviceEnvFleet, FleetStats  # noqa: F401
from repro.data.trajectory import (
    Trajectory,
    buffer_add,
    buffer_drain,
    device_buffer_init,
    split_for_learners,
)
from repro.replay import buffer as replay_buffer
from repro.replay.sharded import ShardedReplay
from repro.rl import losses

PyTree = Any


@dataclasses.dataclass(frozen=True)
class SebulbaConfig:
    num_actor_cores: int = 2  # paper default: 2 actor / 6 learner
    threads_per_actor_core: int = 2  # hide env latency (paper)
    actor_batch_size: int = 32  # envs per actor thread (paper: 32..128)
    trajectory_length: int = 20  # paper: 20 (IMPALA) .. 60
    queue_capacity: int = 4
    discount: float = 0.99
    entropy_cost: float = 0.01
    value_cost: float = 0.5
    clip_rho: float = 1.0
    clip_c: float = 1.0
    learner_microbatches: int = 1  # MuZero batch-splitting trick
    # skip republishing params to an actor core whose previous publish is
    # still unconsumed (the actor acts with the standing slot and the next
    # publish lands instead) — fewer transfers at the cost of up to one
    # actor-pickup interval of extra policy lag when the learner outpaces
    # actors; V-trace absorbs the lag.  A core that is also a learner core
    # is never skipped: its publish moves no bytes.  False -> publish every
    # update.
    publish_throttle: bool = True
    # recurrent agents only (R2D2, Kapturowski et al. 2019): unroll the
    # first ``burn_in`` steps of every trajectory with the stored carry but
    # WITHOUT gradient, refreshing the (stale, recorded-under-old-params)
    # state before the V-trace loss runs on the remaining steps.  Happens
    # inside the agent loss, i.e. inside the compile-cached donated update.
    burn_in: int = 0
    replay: ReplayConfig | None = None  # set -> off-policy (replay) mode
    # actor supervision (repro/core/supervision.py): a crashed actor
    # incarnation is restarted with exponential backoff (restart_backoff *
    # 2**restarts seconds) under a fresh RNG fold; after ``max_restarts``
    # restarts the slot is quarantined and the fleet degrades gracefully.
    # An actor whose heartbeat is older than ``stall_timeout`` seconds is
    # declared hung by the watchdog (cancelled + restarted/quarantined) —
    # size it above worst-case jit-compile + env-step latency.
    max_restarts: int = 3
    restart_backoff: float = 0.05
    stall_timeout: float = 60.0


class Sebulba:
    def __init__(
        self,
        env_factory: Callable[[int], object] = None,  # seed -> host env
        make_batched_env: Callable[[Callable, int], object] = None,
        network=None,
        optimizer: optim.GradientTransformation = None,
        config: SebulbaConfig = SebulbaConfig(),
        devices=None,
        agent=None,
        device_env=None,  # DeviceEnv / factory / ScenarioMix(es) / fleet
        fault_plan=None,  # repro.fault.FaultPlan — chaos test/bench surface
        cluster=None,  # repro.distributed.HostSupervisor — elastic fleet
    ):
        self.cfg = config
        if device_env is None and (env_factory is None or make_batched_env is None):
            raise ValueError(
                "Sebulba needs an environment: either the host pair "
                "(env_factory, make_batched_env) or device_env= (a "
                "repro.api.DeviceEnv, a zero-arg factory, ScenarioMix "
                "entries, or a prebuilt DeviceEnvFleet)"
            )
        if agent is None:
            if config.replay is not None:
                from repro.agents.replay_impala import ReplayImpalaAgent

                agent = ReplayImpalaAgent(network, config)
            else:
                agent = ImpalaAgent(network, config)
        # One protocol, validated once: signature conformance, the
        # zero-carry invariant, and legacy-agent adaptation all live in
        # repro.api — this class never sniffs arities or class markers.
        self._agent_name = type(agent).__name__
        self.agent, self.spec = api.resolve_agent(
            agent, replay_hint=config.replay is not None
        )
        self._recurrent = self.spec.recurrent
        self.opt = optimizer
        self.env_factory = env_factory
        self.make_batched_env = make_batched_env
        self.split: CoreSplit = split_devices(config.num_actor_cores, devices)
        self.learner_mesh = Mesh(list(self.split.learner_devices), ("batch",))
        self.L = self.split.num_learners
        if (config.actor_batch_size % self.L) != 0:
            raise ValueError("actor batch must divide evenly across learners")

        # device-resident env fleet (the Anakin-style regime): the actor
        # loop fuses env.step + agent.act into one donated jit and never
        # syncs actions to the host.  The fleet is sharded L-ways so each
        # learner's slice of the batch carries the same scenario mix.
        self._fleet: DeviceEnvFleet | None = None
        if device_env is not None:
            if isinstance(device_env, DeviceEnvFleet):
                if device_env.num_envs != config.actor_batch_size:
                    raise ValueError(
                        f"device fleet has {device_env.num_envs} envs but "
                        f"actor_batch_size is {config.actor_batch_size}; "
                        "size the fleet to the actor batch"
                    )
                if device_env.shards % self.L:
                    raise ValueError(
                        f"device fleet is laid out in {device_env.shards} "
                        f"scenario blocks, which does not tile across "
                        f"{self.L} learner cores — build the fleet with "
                        "shards equal to (a multiple of) the learner count "
                        "so every learner sees the same scenario mix"
                    )
                self._fleet = device_env
            else:
                self._fleet = DeviceEnvFleet(
                    device_env, config.actor_batch_size, shards=self.L
                )

        self._replay: ShardedReplay | None = None
        if config.replay is not None:
            rcfg = config.replay
            if config.learner_microbatches != 1:
                raise ValueError(
                    "learner_microbatches is an on-policy feature; replay "
                    "mode decouples batch sizes via sample_batch_size"
                )
            if rcfg.capacity % self.L or rcfg.sample_batch_size % self.L:
                raise ValueError(
                    "replay capacity and sample_batch_size must divide "
                    f"across {self.L} learner cores"
                )
            if config.actor_batch_size > rcfg.capacity:
                raise ValueError(
                    "replay capacity must be >= actor_batch_size: each "
                    "update inserts the full online shard, and a ring "
                    "smaller than one insert would write duplicate slots"
                )
            # capability check, not an arity sniff: replay mode needs the
            # declared replay contract (weights in, priorities out).
            # Fail here, not in a jit trace on the first learner update.
            if not self.spec.replay:
                raise ValueError(
                    "replay mode needs agent.loss(params, trajectory, "
                    "importance_weights) returning LossAux(metrics, "
                    f"priorities); {self._agent_name} declares AgentSpec("
                    "replay=False) — declare AgentSpec(replay=True) and "
                    "emit per-sequence priorities for the write-back"
                )
            self._replay = ShardedReplay(
                self.learner_mesh, rcfg.capacity,
                prioritized=rcfg.prioritized,
                priority_exponent=rcfg.priority_exponent,
            )
            # scenario-mix replay strata: per-learner ring slots are
            # written sequentially (insert_slots), so when the local ring
            # capacity is a multiple of the local online shard, slot s
            # permanently holds scenario scenario_ids[s % local_B] — the
            # ring is structurally stratified by scenario, per learner
            if self._fleet is not None and self._fleet.num_scenarios > 1:
                local_cap = rcfg.capacity // self.L
                local_B = config.actor_batch_size // self.L
                if local_cap % local_B:
                    raise ValueError(
                        "scenario-mix replay needs the per-learner ring "
                        f"capacity ({local_cap}) to be a multiple of the "
                        f"per-learner online shard ({local_B}) so replay "
                        "slots stay scenario-pure (each slot always holds "
                        "the same scenario's trajectories); round "
                        "ReplayConfig.capacity accordingly"
                    )
        elif self.spec.replay:
            raise ValueError(
                f"{self._agent_name} requires SebulbaConfig.replay: it "
                "declares AgentSpec(replay=True) — its loss expects "
                "importance weights and emits replay priorities the "
                "on-policy learner has no ring to write back into"
            )

        # slot counts of the structural replay strata (per learner ring),
        # reported through the per-scenario result counters
        self.replay_strata: dict | None = None
        if self._replay is not None and self._fleet is not None:
            local_cap = config.replay.capacity // self.L
            local_B = config.actor_batch_size // self.L
            if local_cap % local_B == 0:
                cycles = local_cap // local_B
                self.replay_strata = {
                    s.name: (self._fleet.rows[i] // self.L) * cycles
                    for i, s in enumerate(self._fleet.scenarios)
                }

        if config.burn_in < 0:
            raise ValueError("burn_in must be >= 0")
        if config.burn_in:
            if not self._recurrent:
                raise ValueError(
                    "burn_in is a recurrent-agent feature (it refreshes the "
                    "stored carry); feed-forward agents have no state to "
                    "burn in"
                )
            if config.burn_in >= config.trajectory_length:
                raise ValueError(
                    f"burn_in ({config.burn_in}) must leave at least one "
                    "trained step: it must be < trajectory_length "
                    f"({config.trajectory_length})"
                )
        # learner updates are built lazily (they need the trajectory
        # structure), cached per trajectory shape, and donated (see
        # ``_donate_state``)
        self._update_cache: dict = {}
        self._update_off = None
        self._update_off_core = None
        self._macc_spec = None  # metrics structure, captured at first update
        self.update_traces = 0  # compile probe: jit traces once per compile

        # the fused actor hot path: one donated-jit program per env step
        # (buffer, rng, and recurrent carry donated -> in-place ring and
        # state writes), one donated-jit drain per trajectory (the outputs
        # alias the donated ring storage)
        self._act_step = jax.jit(self._act_step_fn, donate_argnums=(1, 2, 5))
        # device-env mode: env.step fuses INTO the actor program — buffer,
        # rng, env state, and carry all update in place, and nothing (not
        # even the actions) syncs back to the host per step
        self._device_act_step = (
            jax.jit(self._device_act_step_fn, donate_argnums=(1, 2, 3, 6))
            if self._fleet is not None else None
        )
        self._drain = jax.jit(buffer_drain, donate_argnums=(0,))
        self._split_traj = jax.jit(
            lambda traj: split_for_learners(traj, self.L)
        )

        # host-side state shared between threads.  No locks on the hot path:
        # the params slot is a versioned tuple per actor core (list-item
        # assignment/read are atomic under the GIL) and every other mutable
        # field lives on the incarnation's own ActorHandle — heartbeat,
        # frame/backpressure counters, fleet-stats snapshot — written only
        # by its thread and read by the learner.
        self._params_version = 0
        self._param_slots: list[tuple[int, PyTree]] = (
            [(0, None)] * self.split.num_actors
        )
        # last params version each actor core picked up (stamped by actor
        # threads); drives the overlap-aware publish skip
        self._slot_consumed: list[int] = [0] * self.split.num_actors
        self.publishes_sent = 0
        self.publishes_skipped = 0
        # slot writes that hand an actor core the update's output buffers
        self.publishes_aliased = 0
        # an actor core that is also a learner core (one chip, or a single
        # CPU device) reads the learner's own params buffers through its
        # slot (see _publish_params).  Donate params only where no actor
        # reads them, opt_state always: both updates take the donated
        # learner-state arguments (params=0, opt_state=1) from here.
        self._shared_devices = frozenset(self.split.actor_devices) & frozenset(
            self.split.learner_devices
        )
        self._donate_state = (1,) if self._shared_devices else (0, 1)
        self._queue: queue.Queue = queue.Queue(maxsize=config.queue_capacity)
        self._stop = threading.Event()
        self.episode_returns: deque = deque(maxlen=256)
        # the supervised actor fleet: one slot per (core, thread); slot i's
        # base seed i+1 matches the pre-supervision thread seeds, so a
        # fault-free run is bit-exact with the unsupervised pipeline
        slot_specs = [
            (core, 1 + core * config.threads_per_actor_core + k)
            for core in range(self.split.num_actors)
            for k in range(config.threads_per_actor_core)
        ]
        self.supervisor = ActorSupervisor(
            slots=slot_specs,
            spawn=self._run_actor,
            stop=self._stop,
            max_restarts=config.max_restarts,
            restart_backoff=config.restart_backoff,
            stall_timeout=config.stall_timeout,
            fault_plan=fault_plan,
        )
        self._fault_plan = fault_plan
        # multi-host membership (ISSUE 8): the learner loop polls the
        # cluster each drain iteration and reacts to epoch bumps; actors
        # tag every enqueued trajectory with the epoch they produced it
        # under (one int read — the hot path pays nothing else)
        self._cluster = cluster
        self._epoch = 0
        self.stale_epoch_trajs = 0

    @property
    def frames(self) -> int:
        """Total host env frames generated (summed over every actor
        incarnation the supervisor ever spawned)."""
        return sum(h.frames for h in self.supervisor.handles())

    # -------------------------------------------------------------- setup

    def init(self, rng: jax.Array, obs_shape):
        params = self.agent.init(rng, obs_shape)
        replicated = NamedSharding(self.learner_mesh, P())
        params = jax.device_put(params, replicated)
        opt_state = jax.device_put(self.opt.init(params), replicated)
        self._publish_params(params, force=True)
        return params, opt_state

    def _publish_params(self, params: PyTree, force: bool = False) -> None:
        """Overlap-aware, non-blocking device-to-device publish.

        ``device_put`` only *dispatches* the transfers; the learner thread
        never waits on them.  Each actor core has a versioned slot — a
        (version, params) tuple swapped in one atomic list assignment — so
        actors always read a consistent pair without taking a lock, and the
        versions any actor observes are monotone.

        Publish throttling (``SebulbaConfig.publish_throttle``): a core
        whose consumed stamp trails its slot version has not acted with the
        previous publish yet, so re-publishing would replace params nobody
        ever used — skip the transfer and let the slot stand.  The actor
        consumes the standing slot, its stamp catches up, and the *next*
        publish lands: at most one publish is in flight per core, and
        staleness is bounded by one actor pickup interval.  Skips only
        trigger when the learner outpaces actor pickup; in that regime the
        standing slot can be up to updates-per-actor-step staler than
        publish-every-update would leave it (the transfer saving and the
        extra lag have the same source).  V-trace semantics are unaffected
        either way — behaviour log-probs are recorded from whatever params
        the actor actually used, and the learner's V-trace correction
        absorbs this lag exactly as it absorbs queueing lag; set
        ``publish_throttle=False`` if minimum policy lag matters more than
        publish bandwidth.  A core that is also a learner core is never
        skipped: its slot takes a handle on the learner's params, so the
        publish moves no bytes, and a standing slot would keep an older
        params set alive in device memory beside the learner's.
        """
        self._params_version += 1
        version = self._params_version
        throttle = self.cfg.publish_throttle and not force
        sent = skipped = aliased = 0
        with jax.profiler.TraceAnnotation(
            "sebulba.learner.publish", version=version
        ) as span:
            for i, dev in enumerate(self.split.actor_devices):
                shared = dev in self._shared_devices
                if (throttle and not shared
                        and self._slot_consumed[i] < self._param_slots[i][0]):
                    skipped += 1
                    continue
                # onto a learner core, device_put returns a handle on the
                # update's own output buffers: no copy.  The update does
                # not donate params on such a topology (``_donate_state``),
                # so the slot stays live through the next update.
                self._param_slots[i] = (version, jax.device_put(params, dev))
                sent += 1
                aliased += shared
            span.set_metadata(sent=sent, skipped=skipped, aliased=aliased)
        self.publishes_sent += sent
        self.publishes_skipped += skipped
        self.publishes_aliased += aliased

    # -------------------------------------------------------------- actor

    def _act_step_fn(self, params, buf, rng, obs, rew_disc, carry):
        """The fused per-step actor program: RNG split, episode-boundary
        carry reset, policy inference, log-prob, and the in-place
        trajectory-ring write — one XLA dispatch per env step, with
        ``buf``, ``rng``, and ``carry`` donated.

        ``carry`` is the recurrent state (or () for feed-forward agents, in
        which case this traces to exactly the pre-carry program).  The
        reset rides the discount channel: ``rew_disc[1]`` is zero where the
        previous env step ended an episode, so those batch rows restart
        from the agent's initial state before acting.  The post-reset carry
        is what ``buffer_add`` snapshots at t == 0 — the R2D2 stored state
        for the slice.

        Every agent takes the canonical ``act(params, obs, rng, carry)``
        (repro.api); the reset branch keys on the DECLARED capability at
        trace time, so the protocol adds zero traced ops either way.
        """
        rng, a_rng = jax.random.split(rng)
        if self._recurrent:
            B = rew_disc.shape[1]
            ended = rew_disc[1] == 0.0  # (B,) prev step closed the episode
            init = self.agent.initial_carry(B)
            carry = jax.tree.map(
                lambda c, c0: jnp.where(
                    ended.reshape((B,) + (1,) * (c.ndim - 1)), c0, c
                ),
                carry, init,
            )
        actions, aux, new_carry = self.agent.act(params, obs, a_rng, carry)
        with jax.named_scope("ring_write"):
            buf = buffer_add(
                buf, obs, actions, aux.logp, aux.extras, rew_disc, carry
            )
        return actions, buf, rng, new_carry

    def _initial_carry(self, device):
        """This thread's starting recurrent state on its actor core (() for
        feed-forward agents)."""
        if not self._recurrent:
            return ()
        return jax.device_put(
            self.agent.initial_carry(self.cfg.actor_batch_size), device
        )

    def _make_actor_buffer(self, params, obs_dev, device):
        """Preallocate this thread's device trajectory ring, deriving the
        action/logp/extras/carry storage shapes from the agent's canonical
        act (no tracing side effects — ``eval_shape`` is abstract).  Also
        the one place act's extras structure meets the declared
        ``AgentSpec.extras_keys`` — checked here, once per thread, never
        on the hot path (legacy-adapted agents predate the declaration and
        keep their unchecked pytree extras)."""
        as_spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        obs_spec = jax.tree.map(as_spec, obs_dev)
        carry_spec = jax.tree.map(
            as_spec, self.agent.initial_carry(self.cfg.actor_batch_size)
        )
        act_spec, aux_spec, _ = jax.eval_shape(
            self.agent.act, params, obs_spec, jax.random.key(0), carry_spec
        )
        if not api.is_legacy_adapter(self.agent):
            api.validate_extras(aux_spec.extras, self.spec, self._agent_name)
        buf = device_buffer_init(
            self.cfg.trajectory_length, obs_spec, act_spec, aux_spec.logp,
            aux_spec.extras, carry_spec,
        )
        return jax.device_put(buf, device)

    def _run_actor(self, handle: ActorHandle) -> None:
        """One supervised actor incarnation (the ``ActorSupervisor`` spawn
        body).  Exceptions propagate to the supervisor wrapper, which
        records them — with tracebacks — on the handle for the restart /
        quarantine path; nothing here needs a try/except."""
        if self._fleet is not None:
            self._device_actor_loop(handle)
        else:
            self._actor_loop(handle)

    def _actor_loop(self, handle: ActorHandle) -> None:
        cfg = self.cfg
        device = self.split.actor_devices[handle.core_id]
        seed = handle.seed
        env = self.make_batched_env(
            lambda i: self.env_factory(seed * 10_000 + i), cfg.actor_batch_size
        )
        try:
            self._host_actor_loop(handle, env, device)
        finally:
            # release the env's share of the host stepping pool (the shared
            # ThreadPoolExecutor shuts down with its last reference)
            close = getattr(env, "close", None)
            if callable(close):
                close()

    def _actor_live(self, handle: ActorHandle) -> bool:
        """The actor-loop continuation check: run until shutdown (stop) or
        this incarnation is abandoned by the watchdog (cancel)."""
        return not (self._stop.is_set() or handle.cancel.is_set())

    def _host_actor_loop(self, handle: ActorHandle, env, device) -> None:
        cfg = self.cfg
        obs = env.reset()
        rng = jax.device_put(jax.random.key(handle.seed), device)
        running_return = np.zeros(cfg.actor_batch_size)
        # previous step's [rewards; discounts], batched into ONE transfer
        host_data = np.zeros((2, cfg.actor_batch_size), np.float32)
        buf = None
        carry = self._initial_carry(device)  # recurrent state, or ()
        t = 0  # host mirror of the ring cursor (control flow only, no sync)
        last_version = 0
        traj_version = 0  # params version the ring's first step acted with
        injector = handle.injector

        while self._actor_live(handle):
            # watchdog heartbeat: one monotonic stamp per env step.  A
            # scheduled fault fires AFTER the stamp, so a hang freezes the
            # heartbeat exactly as a real wedged env would.
            handle.beat()
            if injector is not None:
                injector.tick(stop=self._stop, cancel=handle.cancel)
            version, params = self._param_slots[handle.core_id]
            if version != last_version:
                last_version = version
                # stamp consumption so the learner's throttled publish knows
                # this slot was picked up.  The racy read-modify-write across
                # this core's threads is benign: a stale-low stamp lasts one
                # env step at most (the thread re-reads the slot next loop)
                # and only ever delays a publish, never loses one.
                if self._slot_consumed[handle.core_id] < version:
                    self._slot_consumed[handle.core_id] = version
            obs_dev = jax.device_put(obs, device)
            hd_dev = jax.device_put(host_data, device)
            if buf is None:
                buf = self._make_actor_buffer(params, obs_dev, device)
            if t == cfg.trajectory_length:
                # ring full: merge the final step's rewards, hand the
                # trajectory (aliasing the donated ring storage) to the
                # learner shards, and continue on a fresh ring.  The LIVE
                # carry persists across the drain — only the stored
                # snapshot travels with the trajectory.
                traj, buf = self._drain(buf, hd_dev, obs_dev)
                t = 0
                shards = self._shard_for_learners(traj)
                if not self._queue_put(shards, handle, traj_version):
                    return  # stopping — the in-flight trajectory is dropped
            if t == 0:
                traj_version = version
            actions, buf, rng, carry = self._act_step(
                params, buf, rng, obs_dev, hd_dev, carry
            )
            # the one host sync per step: the env needs the actions
            actions_host = np.asarray(actions)
            next_obs, rewards, dones = env.step(actions_host)

            running_return += rewards
            for r in running_return[dones]:
                self.episode_returns.append(float(r))
            running_return[dones] = 0.0

            host_data = np.stack(
                [rewards, (~dones).astype(np.float32) * cfg.discount]
            )
            handle.frames += cfg.actor_batch_size
            obs = next_obs
            t += 1

    # ------------------------------------------------- actor (device envs)

    def _device_act_step_fn(
        self, params, buf, rng, env_state, obs, rew_disc, carry, stats
    ):
        """The fused per-step actor program for device-resident envs: one
        XLA dispatch covering RNG split, carry reset, policy inference, the
        in-place ring write, the BATCHED ENV STEP, and the per-scenario
        stats fold — with ``buf``, ``rng``, ``env_state``, and ``carry``
        donated.  Where the host path syncs actions back for ``env.step``,
        here the env consumes them inside the same program: the device
        actor loop has NO per-step host sync at all.

        ``rew_disc``/``obs`` are this step's inputs and next step's outputs
        (same convention as the host path: the reward/discount written at
        slot t belong to the step that produced obs_t), left undonated so
        the trajectory drain can read them on boundaries.
        """
        rng, a_rng = jax.random.split(rng)
        if self._recurrent:
            B = rew_disc.shape[1]
            ended = rew_disc[1] == 0.0  # prev step closed the episode
            init = self.agent.initial_carry(B)
            carry = jax.tree.map(
                lambda c, c0: jnp.where(
                    ended.reshape((B,) + (1,) * (c.ndim - 1)), c0, c
                ),
                carry, init,
            )
        actions, aux, new_carry = self.agent.act(params, obs, a_rng, carry)
        with jax.named_scope("ring_write"):
            buf = buffer_add(
                buf, obs, actions, aux.logp, aux.extras, rew_disc, carry
            )
        with jax.named_scope("env_step"):
            env_state, ts = self._fleet.step(env_state, actions)
            stats = self._fleet.update_stats(stats, ts)
        # same discount convention as the host path: cfg.discount on live
        # steps, 0 across episode boundaries (the env's discount channel
        # supplies the boundary)
        rew_disc = jnp.stack([
            ts.reward,
            (ts.discount != 0.0).astype(jnp.float32) * self.cfg.discount,
        ])
        return buf, rng, env_state, ts.obs, rew_disc, new_carry, stats

    def _device_actor_loop(self, handle: ActorHandle) -> None:
        cfg = self.cfg
        device = self.split.actor_devices[handle.core_id]
        fleet = self._fleet
        env_key, rng = jax.random.split(jax.random.key(handle.seed))
        env_state = jax.device_put(fleet.init(env_key), device)
        obs = jax.device_put(fleet.observe(env_state), device)
        rew_disc = jax.device_put(
            jnp.zeros((2, cfg.actor_batch_size), jnp.float32), device
        )
        stats = jax.device_put(fleet.init_stats(), device)
        rng = jax.device_put(rng, device)
        carry = self._initial_carry(device)
        buf = None
        t = 0
        last_version = 0
        traj_version = 0
        injector = handle.injector
        try:
            while self._actor_live(handle):
                handle.beat()
                if injector is not None:
                    injector.tick(stop=self._stop, cancel=handle.cancel)
                version, params = self._param_slots[handle.core_id]
                if version != last_version:
                    last_version = version
                    if self._slot_consumed[handle.core_id] < version:
                        self._slot_consumed[handle.core_id] = version
                if buf is None:
                    buf = self._make_actor_buffer(params, obs, device)
                if t == cfg.trajectory_length:
                    traj, buf = self._drain(buf, rew_disc, obs)
                    t = 0
                    # stats is undonated and cumulative: publishing the
                    # handle is the whole snapshot (no copy, no sync)
                    handle.stats = stats
                    shards = self._shard_for_learners(traj)
                    if not self._queue_put(shards, handle, traj_version):
                        return
                if t == 0:
                    traj_version = version
                buf, rng, env_state, obs, rew_disc, carry, stats = (
                    self._device_act_step(
                        params, buf, rng, env_state, obs, rew_disc, carry,
                        stats,
                    )
                )
                handle.frames += cfg.actor_batch_size
                t += 1
        finally:
            handle.stats = stats

    def _queue_put(self, shards, handle: ActorHandle, version: int) -> bool:
        """Blocking put that never silently drops a trajectory.

        Retries on a full queue (counting the blocked intervals so ``run``
        can surface learner back-pressure) until the put lands or the
        system is stopping; every retry re-checks the shared stop event AND
        this incarnation's cancel flag, so a shutdown (or a watchdog
        abandonment) can never leave the put spinning.  Only those exits
        drop the trajectory, and that drop is counted too.  Returns False
        when stopping.

        The entry is ``(epoch, version, traj, shards)``: ``version`` is the
        params version the trajectory's first step acted with (the learner
        reads the policy lag from it) and ``traj`` the trajectory's id,
        ``"<slot>r<incarnation>:<sequence>"``, which the learner's update
        span repeats.
        """
        traj = f"{handle.slot}r{handle.incarnation}:{handle.puts}"
        # retry granularity must beat the watchdog: a put blocked on the
        # learner heartbeats once per retry, so the retry interval has to
        # sit well inside the stall budget or back-pressure reads as a hang
        timeout = min(0.5, self.cfg.stall_timeout / 4)
        with jax.profiler.TraceAnnotation(
            "sebulba.actor.put", traj=traj, version=version
        ):
            while self._actor_live(handle):
                try:
                    # epoch-tagged: the learner drops entries produced
                    # under a stale membership (see run's epoch check)
                    self._queue.put(
                        (self._epoch, version, traj, shards), timeout=timeout
                    )
                    handle.mark_put()
                    return True
                except queue.Full:
                    handle.beat()  # blocked on the learner, not hung
                    handle.put_blocked += 1
        handle.traj_dropped += 1
        return False

    def _shard_for_learners(self, traj: Trajectory):
        """Slice the completed trajectory on the actor core and send each
        shard directly to its learner device (the paper's device-to-device
        trajectory transfer), reassembling the single-device handles as one
        globally-sharded array per leaf.  No trajectory leaf ever becomes
        host numpy on this path."""
        sharding = NamedSharding(self.learner_mesh, P("batch"))
        if self.L == 1:
            return jax.tree.map(lambda x: jax.device_put(x, sharding), traj)

        # one fused program slices every leaf on the actor core ...
        parts = self._split_traj(traj)
        # ... then each slice is copied device-to-device to its learner
        parts = [
            jax.device_put(part, dev)
            for part, dev in zip(parts, self.split.learner_devices)
        ]

        def assemble(*shards):
            global_shape = (
                shards[0].shape[0] * self.L,
            ) + shards[0].shape[1:]
            return jax.make_array_from_single_device_arrays(
                global_shape, sharding, list(shards)
            )

        return jax.tree.map(assemble, *parts)

    # ------------------------------------------------------------- learner

    def _sgd_step(self, params, opt_state, loss_fn):
        """One synchronized step inside shard_map: grad -> cross-shard
        pmean -> optimizer update.  Shared by the on-policy and replay
        learners so the gradient-step sequence exists once.
        """
        # the named scopes label the update's device ops in a profile;
        # inside ``forward_backward`` JAX's own ``jvp(...)`` and
        # ``transpose(jvp(...))`` prefixes tell the two passes apart
        with jax.named_scope("forward_backward"):
            grads, aux = jax.grad(loss_fn, has_aux=True)(params)
        grads = jax.lax.pmean(grads, "batch")
        with jax.named_scope("optimizer"):
            updates, opt_state = self.opt.update(grads, opt_state, params)
            params = optim.apply_updates(params, updates)
        return params, opt_state, aux

    def _build_update(self, example: Trajectory):
        """The shard_map'd on-policy update core for trajectories shaped
        like ``example``: (params, opt_state, traj) -> (params, opt_state,
        metrics).  Un-jitted — ``_get_update`` wraps it with donation and
        the metrics accumulator; keeping the core separate lets callers
        ``jax.eval_shape`` the metrics structure without compiling."""
        cfg = self.cfg

        def shard_update(params, opt_state, traj):
            def micro_step(carry, mb: Trajectory):
                params, opt_state = carry
                params, opt_state, aux = self._sgd_step(
                    params, opt_state, lambda p: self.agent.loss(p, mb)
                )
                metrics = jax.lax.pmean(aux.metrics, "batch")
                return (params, opt_state), metrics

            if cfg.learner_microbatches > 1:
                n = cfg.learner_microbatches
                mbs = jax.tree.map(
                    lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), traj
                )
                (params, opt_state), metrics = jax.lax.scan(
                    micro_step, (params, opt_state), mbs
                )
                metrics = jax.tree.map(jnp.mean, metrics)
            else:
                (params, opt_state), metrics = micro_step(
                    (params, opt_state), traj
                )
            return params, opt_state, metrics

        traj_spec = jax.tree.map(lambda _: P("batch"), example)
        return jax.shard_map(
            shard_update,
            mesh=self.learner_mesh,
            in_specs=(P(), P(), traj_spec),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )

    @staticmethod
    def _traj_key(traj: Trajectory):
        return (
            jax.tree.structure(traj),
            tuple(
                (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
                for leaf in jax.tree.leaves(traj)
            ),
        )

    def _get_update(self, traj: Trajectory):
        """The donated, compile-cached on-policy update for this trajectory
        shape -> (jitted update, core).

        Built once per (structure, shapes, dtypes) key and jitted with
        ``donate_argnums`` covering opt_state, the trajectory shards (they
        alias the actor ring's D2D copies and are dead after the grad
        step), the metrics accumulator, and params where no actor reads
        them (``_donate_state``) — the steady-state learner update reuses
        those buffers in place.
        """
        key = self._traj_key(traj)
        entry = self._update_cache.get(key)
        if entry is None:
            core = self._build_update(traj)

            def update(params, opt_state, traj, macc):
                # trace-time side effect: jit traces exactly once per
                # compile, so this counter is the tests' compile probe
                self.update_traces += 1
                params, opt_state, metrics = core(params, opt_state, traj)
                return params, opt_state, self._macc_add(macc, metrics)

            entry = (
                jax.jit(update, donate_argnums=(*self._donate_state, 2, 3)),
                core,
            )
            self._update_cache[key] = entry
        return entry

    # --------------------------------------------- device-resident metrics

    @staticmethod
    def _macc_add(macc, metrics):
        """Fold one update's metrics into the accumulator (traced inside
        the donated update, so accumulation is in-place on device).  The
        accumulator is ONE packed f32 vector — [count, *metric sums] — so
        it adds a single leaf to the update's dispatch, not one per
        metric."""
        leaves = [x.astype(jnp.float32) for x in jax.tree.leaves(metrics)]
        return macc + jnp.stack([jnp.float32(1.0), *leaves])

    def _fresh_macc(self, metrics_spec=None) -> jax.Array:
        """Zeroed device-resident metrics accumulator, replicated over the
        learner mesh.  Every update folds its metrics into it on device;
        the host reads (and therefore syncs on) it only at ``log_every``
        boundaries — the steady-state learner loop never syncs."""
        if metrics_spec is not None:
            self._macc_spec = jax.tree.structure(metrics_spec)
        zeros = jnp.zeros((1 + self._macc_spec.num_leaves,), jnp.float32)
        return jax.device_put(
            zeros, NamedSharding(self.learner_mesh, P())
        )

    def _drain_macc(self, macc) -> dict | None:
        """Pull the accumulated metric means to host — the one
        device->host sync, paid only on log boundaries.  None if nothing
        has accumulated since the last drain."""
        vals = np.asarray(macc)
        if vals[0] == 0.0:
            return None
        return jax.tree.unflatten(
            self._macc_spec, [float(v) / float(vals[0]) for v in vals[1:]]
        )

    # ------------------------------------------------- learner (off-policy)

    def _build_offpolicy_update(self, example: Trajectory):
        """One fused device step: insert the online shard into the local
        replay ring, sample a replay shard, train on the concatenated mixed
        batch with PER importance weights, write TD priorities back.
        The optimizer state, the replay ring, the metrics accumulator, and
        params where no actor reads them (``_donate_state``) are donated,
        so the learner state updates in place and never leaves the learner
        cores.  Returns (jitted update, core) — the core exists so ``run``
        can ``eval_shape`` the metrics structure.
        """
        cfg = self.cfg
        rcfg = cfg.replay
        local_sample = rcfg.sample_batch_size // self.L

        def shard_update(params, opt_state, rstate, traj, key, update_idx):
            key = jax.random.fold_in(key, jax.lax.axis_index("batch"))
            B_on = traj.actions.shape[0]
            # sample from the PRE-insert ring: the online shard already sits
            # in the mixed batch at weight 1.0, and inserting first would
            # put it at max priority and have the sample double-draw it
            sampled, idx, probs = replay_buffer.sample(
                rstate, key, local_sample,
                prioritized=rcfg.prioritized,
                priority_exponent=rcfg.priority_exponent,
            )
            if rcfg.prioritized:
                w_replay = losses.per_importance_weights(
                    probs, replay_buffer.size(rstate),
                    rcfg.importance_beta(update_idx), axis_name="batch",
                )
                ins_slots = replay_buffer.insert_slots(rstate, B_on)
                rstate = replay_buffer.insert(
                    rstate, traj, axis_name="batch"
                )
            else:
                w_replay = jnp.ones((local_sample,), jnp.float32)
                ins_slots = None
                rstate = replay_buffer.insert(rstate, traj)
            mixed = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), traj, sampled
            )
            weights = jnp.concatenate(
                [jnp.ones((B_on,), jnp.float32), w_replay]
            )

            params, opt_state, aux = self._sgd_step(
                params, opt_state,
                lambda p: self.agent.loss(p, mixed, weights),
            )
            td = aux.priorities  # per-sequence TD magnitudes (AgentSpec.replay)
            metrics = jax.lax.pmean(aux.metrics, "batch")
            if rcfg.prioritized:
                # fresh TD priorities for the sampled replay slots, then the
                # just-inserted online slots (uniform mode never reads
                # priorities — skip the dead scatters on the hot path).  Two
                # sequential scatters, replay first: where the insert
                # overwrote a sampled slot, the slot now holds the fresh
                # trajectory, so its TD must deterministically win
                eps = rcfg.priority_epsilon
                rstate = replay_buffer.update_priorities(
                    rstate, idx, td[B_on:] + eps
                )
                rstate = replay_buffer.update_priorities(
                    rstate, ins_slots, td[:B_on] + eps
                )
            return params, opt_state, rstate, metrics

        rspec = self._replay.state_spec(example)
        tspec = self._replay.batch_spec(example)
        core = jax.shard_map(
            shard_update,
            mesh=self.learner_mesh,
            in_specs=(P(), P(), rspec, tspec, P(), P()),
            out_specs=(P(), P(), rspec, P()),
            check_vma=False,
        )

        def update(params, opt_state, rstate, traj, macc, key, update_idx):
            self.update_traces += 1  # compile probe (see _get_update)
            params, opt_state, rstate, metrics = core(
                params, opt_state, rstate, traj, key, update_idx
            )
            return params, opt_state, rstate, self._macc_add(macc, metrics)

        return jax.jit(
            update, donate_argnums=(*self._donate_state, 2, 4)
        ), core

    def _scenario_snapshot(self):
        """Aggregate the per-thread FleetStats snapshots into the
        per-scenario counters dict (plus the overall mean completed-episode
        return).  Reads — and therefore syncs on — the snapshot arrays, so
        callers only hit this on log/result boundaries."""
        snaps = [
            h.stats for h in self.supervisor.handles() if h.stats is not None
        ]
        if not snaps:
            return {}, float("nan")
        # threads on different actor cores hold stats on different devices;
        # pull each snapshot to host before summing (this IS the boundary
        # sync the docstring describes)
        snaps = [jax.device_get(s) for s in snaps]
        total = jax.tree.map(lambda *xs: sum(xs), *snaps)
        scenarios = self._fleet.stats_summary(total)
        if self.replay_strata:
            for name, slots in self.replay_strata.items():
                scenarios[name]["replay_slots"] = slots
        eps = sum(v["episodes"] for v in scenarios.values())
        rets = sum(v["return_sum"] for v in scenarios.values())
        return scenarios, (rets / eps if eps else float("nan"))

    # ----------------------------------------------------------------- run

    def run(
        self,
        rng: jax.Array,
        obs_shape,
        total_frames: int,
        log_every: int = 0,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        restore_from: str | None = None,
        auto_resume: bool = False,
    ) -> dict:
        """Train until ``total_frames`` host env frames have been generated.

        Returns the unified Podracer result schema (``repro.api.runner``).
        ``checkpoint_dir``/``checkpoint_every`` make the runner persist
        ``param_version``-stamped checkpoints every N learner updates
        (plus a final one); ``restore_from`` warm-starts params from a
        checkpoint file or directory before training (the optimizer state
        restarts fresh — research-checkpoint semantics — while the version
        line and cumulative update/frame stamps continue from the
        checkpoint, so resuming into the same directory keeps
        ``latest_checkpoint`` honest).  ``auto_resume=True`` scans
        ``checkpoint_dir`` and restores from the newest VALID stamp when
        one exists (corrupt files are skipped and counted as
        ``checkpoint_fallbacks``), starting fresh on an empty directory —
        the preemption-recovery entry point.  Checkpoint
        writes sync params to host, so like metric drains they only ever
        happen on boundaries, never in the steady-state donated loop.

        Actor threads run under :class:`~repro.core.supervision.\
ActorSupervisor`: a crashed actor restarts with exponential backoff
        (fresh RNG fold, current published params), a slot exceeding
        ``cfg.max_restarts`` is quarantined while the surviving actors
        keep feeding every learner shard, and a hung actor (heartbeat
        older than ``cfg.stall_timeout``) is cancelled by the watchdog.
        Only when NO actor can make progress does the learner raise
        :class:`SebulbaStallError` with the full diagnostics snapshot and
        every recorded traceback.
        """
        cfg = self.cfg
        params, opt_state = self.init(rng, obs_shape)
        restore_from = api.resolve_auto_resume(
            restore_from, checkpoint_dir, auto_resume
        )
        base_updates = base_frames = 0
        checkpoint_fallbacks = 0
        if restore_from is not None:
            params, opt_state, meta = api.restore_for_fit(
                restore_from, params, self.opt,
                NamedSharding(self.learner_mesh, P()),
            )
            # continue the checkpoint's version line (and cumulative
            # update/frame stamps) so new saves sort ABOVE the restored
            # one — otherwise a resume into the same checkpoint_dir would
            # stamp below it and latest_checkpoint would keep resolving
            # to the stale pre-restore params
            self._params_version = meta["param_version"]
            base_updates = meta["updates"]
            base_frames = meta["frames"]
            checkpoint_fallbacks = meta.get("fallbacks", 0)
            self._publish_params(params, force=True)
        ckpt = api.CheckpointPolicy(
            checkpoint_dir, checkpoint_every, base_updates=base_updates,
            fault=(
                self._fault_plan.checkpoint_injector()
                if self._fault_plan is not None else None
            ),
            span="sebulba.learner.checkpoint",
        )

        if self._cluster is not None:
            # join the fleet before actors produce: the baseline epoch
            # tags every trajectory from the first drain onward
            self._epoch = self._cluster.start().epoch
        self.supervisor.start()

        updates = 0
        lag_sum = lag_max = 0  # policy lag over the updates made
        last_metrics: dict = {}
        macc = None  # device-resident metrics accumulator (init at 1st update)
        replay_state = None
        replay_warmed = False  # size() is monotone: check device once, latch
        replay_rng = jax.random.fold_in(rng, 0x5EB)  # decorrelate from init
        t0 = time.time()
        try:
            while self.frames < total_frames:
                # supervision is learner-driven: every drain iteration
                # (<= ~1 s apart) reaps dead incarnations, fires the
                # heartbeat watchdog, and executes due restarts — no
                # monitor thread, no locks on the actor hot path
                self.supervisor.poll()
                if self._cluster is not None:
                    # host-tier supervision: fire due host chaos, observe
                    # the live set, and on an epoch bump force-republish
                    # so every actor's next step runs the current params
                    # under the current membership (the epoch-checked
                    # publish path)
                    bumped = self._cluster.poll(updates)
                    if bumped is not None:
                        self._epoch = bumped.epoch
                        self._publish_params(params, force=True)
                try:
                    # short poll so supervision stays responsive even when
                    # no actor is producing
                    with jax.profiler.TraceAnnotation("sebulba.learner.get"):
                        epoch_tag, version, traj_id, shards = self._queue.get(
                            timeout=0.5
                        )
                except queue.Empty:
                    # re-poll before judging progress: the snapshot from the
                    # top of the iteration is up to a get-timeout stale, and
                    # a death in that window must be reaped into the
                    # restarting state (which counts as progress), not
                    # mistaken for a dead fleet
                    self.supervisor.poll()
                    if not self.supervisor.can_progress():
                        # every slot is quarantined/stopped (or hung past
                        # its stall budget): the queue will never fill
                        # again.  Raise the structured stall error instead
                        # of polling forever.
                        raise self.supervisor.stall_error(
                            queue_depth=self._queue.qsize(),
                            param_versions=[
                                v for v, _ in self._param_slots
                            ],
                            frames=self.frames,
                            updates=updates,
                        )
                    continue
                if epoch_tag != self._epoch:
                    # epoch-checked insert: this trajectory was produced
                    # (and its replay routing would be computed) under a
                    # membership that no longer exists — count and drop
                    # rather than train across the reshard boundary
                    self.stale_epoch_trajs += 1
                    continue
                # with replay, the trajectory is the one this update
                # inserts; the batch it trains on is sampled from the buffer
                lag = self._params_version - version
                span = jax.profiler.TraceAnnotation(
                    "sebulba.learner.update", traj=traj_id, version=version,
                    lag=lag,
                )
                if self._replay is not None:
                    if replay_state is None:
                        replay_state = self._replay.init(shards)
                        self._update_off, self._update_off_core = (
                            self._build_offpolicy_update(shards)
                        )
                    if not replay_warmed:
                        # warmup: fill the ring before learning starts.  The
                        # size() read syncs device->host, so latch the result
                        # rather than re-reading it in the steady-state loop
                        # (it would serialize every donated async update).
                        if self._replay.size(replay_state) < cfg.replay.min_size:
                            replay_state = self._replay.insert(
                                replay_state, shards
                            )
                            continue
                        replay_warmed = True
                    key = jax.random.fold_in(replay_rng, updates)
                    if macc is None:
                        macc = self._fresh_macc(jax.eval_shape(
                            self._update_off_core, params, opt_state,
                            replay_state, shards, key, jnp.int32(0),
                        )[3])
                    with span:
                        params, opt_state, replay_state, macc = (
                            self._update_off(
                                params, opt_state, replay_state, shards,
                                macc, key, jnp.int32(updates),
                            )
                        )
                else:
                    update, core = self._get_update(shards)
                    if macc is None:
                        macc = self._fresh_macc(jax.eval_shape(
                            core, params, opt_state, shards
                        )[2])
                    with span:
                        params, opt_state, macc = update(
                            params, opt_state, shards, macc
                        )
                self._publish_params(params)
                if self._shared_devices:
                    # params are not donated here, so an update queued on
                    # the device holds a fresh params set of its own: let
                    # the update finish before the next is dispatched, and
                    # the device holds no more params sets than donation
                    # left it (the actors' queued act steps keep it busy)
                    jax.block_until_ready(params)
                updates += 1
                lag_sum += lag
                lag_max = max(lag_max, lag)
                ckpt.maybe_save(
                    params, param_version=self._params_version,
                    updates=base_updates + updates,
                    frames=base_frames + self.frames,
                )
                if log_every and updates % log_every == 0:
                    with jax.profiler.TraceAnnotation("sebulba.learner.log"):
                        m = self._drain_macc(macc)
                        if m is not None:
                            last_metrics = m
                            macc = self._fresh_macc()
                        if self._fleet is not None:
                            _, ret = self._scenario_snapshot()
                        else:
                            ret = (
                                np.mean(self.episode_returns)
                                if self.episode_returns else float("nan")
                            )
                        print(
                            f"update {updates} frames {self.frames} "
                            f"return {ret:.2f} "
                            f"policy_lag={lag_sum / updates:.2f}/{lag_max} " +
                            " ".join(
                                f"{k}={v:.3f}"
                                for k, v in last_metrics.items()
                            )
                        )
        finally:
            self._stop.set()
            if self._cluster is not None:
                self._cluster.stop()  # graceful leave: retire our lease
            leaked = self.supervisor.join(timeout=10.0)
            if leaked:
                # a thread that survives stop+cancel+join is wedged beyond
                # recovery (e.g. a truly hung env).  It is daemonic, so the
                # process can still exit — but report it rather than
                # pretending shutdown was clean.
                warnings.warn(
                    "Sebulba shutdown leaked actor threads (still running "
                    f"after stop/cancel/join): {', '.join(leaked)}",
                    RuntimeWarning,
                    stacklevel=2,
                )

        if macc is not None:
            m = self._drain_macc(macc)
            if m is not None:
                last_metrics = m
        ckpt.final_save(
            params, param_version=self._params_version,
            updates=base_updates + updates, frames=base_frames + self.frames,
        )
        dt = time.time() - t0
        if self._fleet is not None:
            scenarios, mean_return = self._scenario_snapshot()
        else:
            scenarios = {}
            mean_return = (
                float(np.mean(self.episode_returns))
                if self.episode_returns else float("nan")
            )
        return api.make_result(
            params=params,
            updates=updates,
            frames=self.frames,
            seconds=dt,
            metrics=last_metrics,
            mean_return=mean_return,
            scenarios=scenarios,
            # logical publish version actors observe via the versioned
            # slots: init's publish + one per learner update (throttled
            # cores skip transfers, not versions)
            param_version=self._params_version,
            publishes_sent=self.publishes_sent,
            publishes_skipped=self.publishes_skipped,
            publishes_aliased=self.publishes_aliased,
            # learner back-pressure / shutdown accounting (the actor loop
            # retries full-queue puts instead of dropping); sums span every
            # incarnation the supervisor ever spawned
            put_blocked=sum(
                h.put_blocked for h in self.supervisor.handles()
            ),
            traj_dropped=sum(
                h.traj_dropped for h in self.supervisor.handles()
            ),
            # supervision accounting (ISSUE 7): absent-as-0 counters
            actor_restarts=self.supervisor.actor_restarts,
            actor_quarantined=self.supervisor.actor_quarantined,
            watchdog_stalls=self.supervisor.watchdog_stalls,
            checkpoint_fallbacks=checkpoint_fallbacks,
            # multi-host elasticity accounting (ISSUE 8): zeros when no
            # cluster is mounted — one result shape either way
            hosts_joined=(
                self._cluster.hosts_joined if self._cluster else 0
            ),
            hosts_lost=self._cluster.hosts_lost if self._cluster else 0,
            reshards=self._cluster.reshards if self._cluster else 0,
            epoch=self._epoch,
            replay_size=(
                self._replay.size(replay_state)
                if self._replay is not None and replay_state is not None
                else 0
            ),
            checkpoints_saved=ckpt.saved,
            # params versions between the one a trajectory's first step
            # acted with and the one its update starts from
            policy_lag_mean=lag_sum / updates if updates else 0.0,
            policy_lag_max=lag_max,
        )

    def fit(
        self,
        rng: jax.Array,
        total_frames: int,
        *,
        obs_shape=None,
        log_every: int = 0,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        restore_from: str | None = None,
        auto_resume: bool = False,
    ) -> dict:
        """The unified ``repro.api.Runner`` entry point (same loop as
        ``run``).  ``obs_shape`` defaults to what the env factory reports:
        a probe env is constructed for its ``.obs_shape`` and closed if it
        supports closing — pass ``obs_shape`` explicitly when env
        construction is expensive."""
        if obs_shape is None:
            if self._fleet is not None:
                obs_shape = self._fleet.obs_shape
            else:
                probe = self.env_factory(0)
                obs_shape = probe.obs_shape
                close = getattr(probe, "close", None)
                if callable(close):
                    close()
        return self.run(
            rng, obs_shape, total_frames, log_every=log_every,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            restore_from=restore_from, auto_resume=auto_resume,
        )
