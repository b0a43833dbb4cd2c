"""Anakin — online learning with the environment ON the accelerator.

Paper Fig. 2, reproduced exactly:

    def step_and_update_fn(...):
        # 1) step the agent and environment N times
        # 2) compute the loss or other RL objective
        # 3) differentiate back through the entire loop

    batched_fn    = jax.vmap(step_and_update)     # fill a TPU core
    iterated_fn   = jax.lax.fori_loop(batched_fn) # stay out of Python
    replicated_fn = <replicate across cores>      # paper: jax.pmap

Two replication paths are provided:

  * ``mode="shard_map"`` (paper-faithful): explicit SPMD via jax.shard_map
    over a 1-D device mesh with an explicit ``jax.lax.pmean`` on the
    gradients — the modern spelling of the paper's ``pmap`` + ``pmean``.
  * ``mode="jit"``: jit + NamedSharding on the batch dimension; XLA GSPMD
    inserts the gradient all-reduce automatically.  Same program, modern
    idiom — kept separate so EXPERIMENTS.md §Perf can compare both.

Properties preserved from the paper: zero host<->device transfers inside
the training loop (env state lives on device), zero Python in the hot loop
(``iterations`` steps run inside one XLA program via lax.scan), and bitwise
determinism given a seed.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import numpy as np

from repro import api, optim
from repro.envs.device_env import DeviceEnvFleet
from repro.rl import losses

PyTree = Any


@dataclasses.dataclass(frozen=True)
class AnakinConfig:
    unroll_length: int = 16  # N env steps per update
    batch_per_device: int = 32  # parallel envs per core (vmap width)
    iterations_per_call: int = 16  # updates fused into one XLA program
    entropy_cost: float = 0.01
    value_cost: float = 0.5
    td_lambda: float = 0.9
    mode: str = "shard_map"  # "shard_map" (paper-faithful) | "jit"


class AnakinState(NamedTuple):
    params: PyTree
    opt_state: PyTree
    env_state: PyTree  # (num_devices * batch_per_device, ...)
    obs: jax.Array
    rng: jax.Array  # per-env keys
    step: jax.Array


class Anakin:
    """env + network + optimizer -> a fully-on-device online learner."""

    def __init__(
        self,
        env,
        network,  # .init(rng, obs_shape) -> params; .apply(params, obs) -> (logits, value)
        optimizer: optim.GradientTransformation,
        config: AnakinConfig = AnakinConfig(),
        devices=None,
    ):
        self.env = env
        self.net = network
        self.opt = optimizer
        self.cfg = config
        devices = list(devices if devices is not None else jax.devices())
        self.mesh = Mesh(devices, ("batch",))
        self.num_devices = len(devices)
        self.global_batch = self.num_devices * config.batch_per_device
        # scenario-mix fleet support: Anakin reuses the same DeviceEnvFleet
        # Sebulba's device actor drives.  The per-env vmap path stays
        # untouched for single envs; a fleet swaps in the batched unroll
        # (the fleet steps the whole row batch, heterogeneous scenarios
        # included, inside the scan).
        self._fleet = env if isinstance(env, DeviceEnvFleet) else None
        if self._fleet is not None:
            if self._fleet.num_envs != self.global_batch:
                raise ValueError(
                    f"fleet has {self._fleet.num_envs} envs but Anakin's "
                    f"global batch is {self.global_batch} ({self.num_devices}"
                    f" devices x batch_per_device {config.batch_per_device})"
                )
            if self._fleet.shards % self.num_devices:
                raise ValueError(
                    f"fleet is laid out in {self._fleet.shards} scenario "
                    f"blocks, which does not tile across {self.num_devices} "
                    "devices — build it with shards equal to (a multiple "
                    "of) the device count"
                )
            # shard_map sees per-device slices, so the loss steps a LOCAL
            # fleet whose block layout matches this device's slice of the
            # global rows (jit/GSPMD mode operates on the global batch)
            self._loss_fleet = (
                DeviceEnvFleet(
                    self._fleet.scenarios, config.batch_per_device,
                    shards=self._fleet.shards // self.num_devices,
                )
                if config.mode == "shard_map" else self._fleet
            )
        self._run = self._build()

    # ------------------------------------------------------------------

    def init_state(self, rng: jax.Array) -> AnakinState:
        rng, net_rng = jax.random.split(rng)
        params = self.net.init(net_rng, self.env.obs_shape)
        opt_state = self.opt.init(params)
        env_rngs = jax.random.split(rng, self.global_batch)
        if self._fleet is not None:
            # the fleet splits its own per-row keys; env_rngs stay the
            # per-row ACTION keys either way
            env_state = self.env.init(jax.random.fold_in(rng, 1))
            obs = self.env.observe(env_state)
        else:
            env_state = jax.vmap(self.env.init)(env_rngs)
            obs = jax.vmap(self.env.observe)(env_state)
        state = AnakinState(
            params=params,
            opt_state=opt_state,
            env_state=env_state,
            obs=obs,
            rng=env_rngs,
            step=jnp.zeros((), jnp.int32),
        )
        # place: params/opt replicated, env/obs/rng sharded over the batch axis
        batch_sharded = NamedSharding(self.mesh, P("batch"))
        replicated = NamedSharding(self.mesh, P())
        return AnakinState(
            params=jax.device_put(state.params, replicated),
            opt_state=jax.device_put(state.opt_state, replicated),
            env_state=jax.device_put(state.env_state, batch_sharded),
            obs=jax.device_put(state.obs, batch_sharded),
            rng=jax.device_put(state.rng, batch_sharded),
            step=jax.device_put(state.step, replicated),
        )

    # ------------------------------------------------------------------

    def _unroll_and_loss(self, params, env_state, obs, rng):
        """The paper's minimal unit (top of Fig. 2), for ONE environment.

        Steps the env ``unroll_length`` times and computes the A2C loss;
        differentiating this function differentiates back through the whole
        interaction loop.  Called under vmap (batch) and grad.
        """
        cfg = self.cfg

        def one_step(carry, _):
            env_state, obs, rng = carry
            rng, a_rng = jax.random.split(rng)
            logits, value = self.net.apply(params, obs)
            action = jax.random.categorical(a_rng, logits)
            env_state, ts = self.env.step(env_state, action)
            out = (logits, value, action, ts.reward, ts.discount)
            return (env_state, ts.obs, rng), out

        (env_state, obs, rng), (logits, values, actions, rewards, discounts) = (
            jax.lax.scan(one_step, (env_state, obs, rng), None, cfg.unroll_length)
        )
        _, bootstrap = self.net.apply(params, obs)
        return (
            (logits, values, actions, rewards, discounts, bootstrap),
            (env_state, obs, rng),
        )

    def _fleet_unroll(self, fleet, params, env_state, obs, rng):
        """The batched twin of ``_unroll_and_loss``: the fleet steps its
        whole row batch (a heterogeneous scenario portfolio) inside the
        scan, so one program drives every scenario.  Per-row action keys
        split in lockstep; outputs are transposed to the (B, T, ...) the
        loss expects."""
        cfg = self.cfg
        apply = jax.vmap(self.net.apply, in_axes=(None, 0))

        def one_step(carry, _):
            env_state, obs, rng = carry
            keys = jax.vmap(jax.random.split)(rng)  # (B, 2)
            rng, a_rng = keys[:, 0], keys[:, 1]
            logits, values = apply(params, obs)
            actions = jax.vmap(jax.random.categorical)(a_rng, logits)
            env_state, ts = fleet.step(env_state, actions)
            out = (logits, values, actions, ts.reward, ts.discount)
            return (env_state, ts.obs, rng), out

        (env_state, obs, rng), outs = jax.lax.scan(
            one_step, (env_state, obs, rng), None, cfg.unroll_length
        )
        logits, values, actions, rewards, discounts = jax.tree.map(
            lambda x: jnp.swapaxes(x, 0, 1), outs
        )
        _, bootstrap = apply(params, obs)
        return (
            (logits, values, actions, rewards, discounts, bootstrap),
            (env_state, obs, rng),
        )

    def _loss_fn(self, params, env_state, obs, rng):
        cfg = self.cfg
        if self._fleet is not None:
            (logits, values, actions, rewards, discounts, bootstrap), carry = (
                self._fleet_unroll(
                    self._loss_fleet, params, env_state, obs, rng
                )
            )
        else:
            # vmap the minimal unit over this device's batch of environments
            (logits, values, actions, rewards, discounts, bootstrap), carry = jax.vmap(
                self._unroll_and_loss, in_axes=(None, 0, 0, 0)
            )(params, env_state, obs, rng)
        # (B, T, ...) — exactly what the loss wants
        out = losses.a2c_loss(
            logits, values, actions, rewards, discounts, bootstrap,
            entropy_cost=cfg.entropy_cost, value_cost=cfg.value_cost,
            td_lambda=cfg.td_lambda,
        )
        metrics = {
            "loss": out.total, "pg": out.pg, "value": out.value,
            "entropy": out.entropy, "reward": jnp.mean(rewards),
            "episodes": jnp.sum(discounts == 0.0),
        }
        if self._fleet is not None:
            # per-scenario RATES (per row per step), so the values are
            # invariant under the cross-replica pmean (every replica holds
            # the same scenario composition) and identical in both modes
            lf = self._loss_fleet
            seg = jnp.asarray(lf.scenario_ids)
            denom = jnp.asarray(
                np.array(lf.rows, np.float32) * rewards.shape[1]
            )
            metrics["reward_per_scenario"] = (
                jax.ops.segment_sum(
                    jnp.sum(rewards, axis=1), seg, lf.num_scenarios
                ) / denom
            )
            metrics["episodes_per_scenario"] = (
                jax.ops.segment_sum(
                    jnp.sum((discounts == 0.0).astype(jnp.float32), axis=1),
                    seg, lf.num_scenarios,
                ) / denom
            )
        return out.total, (carry, metrics)

    def _update_once(self, state: AnakinState, sync: Callable) -> tuple[AnakinState, dict]:
        grads, (carry, metrics) = jax.grad(self._loss_fn, has_aux=True)(
            state.params, state.env_state, state.obs, state.rng
        )
        grads = sync(grads)  # pmean across replicas (paper's psum/pmean)
        metrics = sync(metrics)
        env_state, obs, rng = carry
        updates, opt_state = self.opt.update(grads, state.opt_state, state.params)
        params = optim.apply_updates(state.params, updates)
        return (
            AnakinState(params, opt_state, env_state, obs, rng, state.step + 1),
            metrics,
        )

    def _build(self):
        cfg = self.cfg

        def iterated(state: AnakinState, sync) -> tuple[AnakinState, dict]:
            # fori_loop/scan over many updates: no Python in the hot loop
            def body(state, _):
                return self._update_once(state, sync)

            state, metrics = jax.lax.scan(
                body, state, None, cfg.iterations_per_call
            )
            # reduce the per-iteration metrics stack on device: one value
            # per metric leaves the compiled block instead of an
            # (iterations,) stack per metric per call (axis 0 only, so the
            # (S,) per-scenario vectors keep their scenario axis)
            return state, jax.tree.map(
                lambda x: jnp.mean(x, axis=0), metrics
            )

        if cfg.mode == "shard_map":
            def sync(tree):
                return jax.lax.pmean(tree, "batch")

            @functools.partial(jax.jit, donate_argnums=0)
            def run(state):
                fn = jax.shard_map(
                    lambda s: iterated(s, sync),
                    mesh=self.mesh,
                    in_specs=(AnakinState(
                        params=P(), opt_state=P(), env_state=P("batch"),
                        obs=P("batch"), rng=P("batch"), step=P(),
                    ),),
                    out_specs=(
                        AnakinState(
                            params=P(), opt_state=P(), env_state=P("batch"),
                            obs=P("batch"), rng=P("batch"), step=P(),
                        ),
                        P(),
                    ),
                    check_vma=False,
                )
                return fn(state)

            return run

        if cfg.mode == "jit":
            batch_sharded = NamedSharding(self.mesh, P("batch"))
            replicated = NamedSharding(self.mesh, P())
            shardings = AnakinState(
                params=replicated, opt_state=replicated,
                env_state=batch_sharded, obs=batch_sharded, rng=batch_sharded,
                step=replicated,
            )

            @functools.partial(jax.jit, donate_argnums=0)
            def run(state):
                state = jax.lax.with_sharding_constraint(state, shardings)
                return iterated(state, lambda tree: tree)

            return run

        raise ValueError(f"unknown anakin mode {cfg.mode!r}")

    # ------------------------------------------------------------------

    def run(self, state: AnakinState, num_calls: int = 1):
        """Run ``num_calls`` compiled blocks of ``iterations_per_call`` updates.

        The compiled block DONATES its input state — (params, opt_state,
        env_state, obs, rng) update in place instead of double-buffering
        the whole pytree, halving peak state memory for large env batches.
        Callers must chain the returned state (``state, m = ank.run(state)``)
        and not touch the donated-away input afterwards.  Metrics come back
        as on-device scalars already averaged over the block's iterations.
        """
        metrics = None
        for _ in range(num_calls):
            state, metrics = self._run(state)
        return state, metrics

    def fit(
        self,
        rng: jax.Array,
        total_frames: int,
        *,
        log_every: int = 0,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        restore_from: str | None = None,
        auto_resume: bool = False,
    ) -> dict:
        """The unified ``repro.api.Runner`` entry point: init (or
        ``restore_from``; ``auto_resume=True`` restores from the newest
        VALID stamp in ``checkpoint_dir`` when one exists — corrupt files
        are skipped and surface as the ``checkpoint_fallbacks`` counter),
        run enough compiled blocks to cover
        ``total_frames`` env steps, checkpoint every ``checkpoint_every``
        updates, and return the unified Podracer result schema.

        Counters that only exist on decomposed architectures (publishes,
        queue back-pressure, replay) are reported as 0 — Anakin has one
        program and no transport.  ``param_version`` is the update count:
        every optimizer step is a new logical params version, there being
        no publish step for versions to lag behind (cumulative across
        ``restore_from``, so resumed stamps keep sorting above the
        restored checkpoint's).  ``log_every`` is in
        learner updates, rounded up to the compiled-block granularity
        (``iterations_per_call`` updates per host visit — metrics are
        means over each block, already reduced on device).
        """
        cfg = self.cfg
        state = self.init_state(rng)
        restore_from = api.resolve_auto_resume(
            restore_from, checkpoint_dir, auto_resume
        )
        base_updates = base_frames = 0
        checkpoint_fallbacks = 0
        if restore_from is not None:
            params, opt_state, meta = api.restore_for_fit(
                restore_from, state.params, self.opt,
                NamedSharding(self.mesh, P()),
            )
            state = state._replace(params=params, opt_state=opt_state)
            # continue the checkpoint's version line so new stamps sort
            # above the restored one (see Sebulba.run)
            base_updates = meta["param_version"]
            base_frames = meta["frames"]
            checkpoint_fallbacks = meta.get("fallbacks", 0)
        ckpt = api.CheckpointPolicy(
            checkpoint_dir, checkpoint_every, base_updates=base_updates
        )
        frames_per_call = self.steps_per_call
        num_calls = api.updates_for_frames(total_frames, frames_per_call)
        metrics = None
        # round UP to block granularity, as documented: log_every=150 with
        # 100-update blocks logs every 200 updates, not every 100
        calls_per_log = max(1, -(-log_every // cfg.iterations_per_call))
        t0 = time.time()
        for call in range(num_calls):
            state, metrics = self._run(state)
            updates = base_updates + (call + 1) * cfg.iterations_per_call
            ckpt.maybe_save(
                state.params, param_version=updates, updates=updates,
                frames=base_frames + (call + 1) * frames_per_call,
            )
            if log_every and (call + 1) % calls_per_log == 0:
                drained = {
                    k: float(v) for k, v in metrics.items()
                    if np.ndim(v) == 0
                }
                # both counters cumulative — `updates` already includes the
                # restored base, so frames must too or resumed logs read
                # as a frames-per-update collapse
                print(
                    f"update {updates} frames "
                    f"{base_frames + (call + 1) * frames_per_call} " +
                    " ".join(f"{k}={v:.3f}" for k, v in drained.items())
                )
        updates = num_calls * cfg.iterations_per_call
        frames = num_calls * frames_per_call
        ckpt.final_save(
            state.params, param_version=base_updates + updates,
            updates=base_updates + updates, frames=base_frames + frames,
        )
        dt = time.time() - t0
        drained = (
            {k: float(v) for k, v in metrics.items() if np.ndim(v) == 0}
            if metrics else {}
        )
        # fleet mode: the (S,) per-scenario rate metrics become the unified
        # ``scenarios`` result key (rates over the final compiled block;
        # Sebulba reports exact cumulative counters on its side)
        scenarios = {}
        if self._fleet is not None and metrics is not None:
            rew = np.asarray(metrics["reward_per_scenario"])
            eps = np.asarray(metrics["episodes_per_scenario"])
            for i, s in enumerate(self._fleet.scenarios):
                scenarios[s.name] = {
                    "weight": s.weight,
                    "rows": self._fleet.rows[i],
                    "reward_per_step": float(rew[i]),
                    "episodes_per_step": float(eps[i]),
                }
        result = api.make_result(
            params=state.params,
            updates=updates,
            frames=frames,
            seconds=dt,
            metrics=drained,
            scenarios=scenarios,
            param_version=base_updates + updates,
            checkpoints_saved=ckpt.saved,
            checkpoint_fallbacks=checkpoint_fallbacks,
        )
        # architecture-specific extra: the full donated AnakinState, so
        # callers can keep stepping the compiled block where fit left off
        result["state"] = state
        return result

    @property
    def steps_per_call(self) -> int:
        """Env steps per compiled call (the FPS numerator)."""
        return (
            self.cfg.iterations_per_call
            * self.cfg.unroll_length
            * self.global_batch
        )
