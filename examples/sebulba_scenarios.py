"""Sebulba training one agent across a weighted scenario portfolio of
device-resident envs — "as many scenarios as you can imagine as a config,
not a fork" (ROADMAP).

Three Pong difficulties share one policy: the fleet seats each scenario on
a weighted share of the actor batch (largest-remainder apportionment), the
fused env+act step runs the whole portfolio in one donated jit per step,
and per-scenario episode/return counters flow through the unified result
schema (``repro.api.RESULT_KEYS``'s ``scenarios`` entry).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/sebulba_scenarios.py --frames 50000
"""

import argparse

import jax

from repro import optim
from repro.agents.impala import ConvActorCritic
from repro.api import ScenarioMix
from repro.compile_cache import enable_compile_cache
from repro.core.sebulba import Sebulba, SebulbaConfig
from repro.envs import Pong


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=50_000)
    ap.add_argument("--actor-cores", type=int, default=2)
    ap.add_argument("--actor-batch", type=int, default=32)
    ap.add_argument("--trajectory", type=int, default=20)
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject a seeded FaultPlan (random crashes/"
                         "stragglers across the device-env actor fleet) to "
                         "exercise supervision under the scenario mix")
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    actor_cores = min(args.actor_cores, max(1, n_dev - 1)) if n_dev > 1 else 1
    learners = max(n_dev - actor_cores, 1)
    actor_batch = -(-args.actor_batch // learners) * learners
    if actor_batch != args.actor_batch:
        print(f"actor batch {args.actor_batch} -> {actor_batch} "
              f"(multiple of {learners} learners)")
    print(f"devices: {n_dev} -> {actor_cores} actor / "
          f"{learners} learner cores")

    # one agent, three difficulties: weights set the share of fleet rows
    # (and so of training frames) each scenario receives
    scenarios = [
        ScenarioMix("sprint", 2.0, lambda: Pong(max_lives=1)),
        ScenarioMix("rally", 1.0, lambda: Pong(max_lives=3)),
        ScenarioMix("marathon", 1.0, lambda: Pong(max_lives=5)),
    ]

    threads_per_core = 2
    fault_plan = None
    chaos_kwargs = {}
    if args.chaos is not None:
        from repro.fault import FaultPlan

        horizon = max(
            20,
            args.frames // (actor_cores * threads_per_core * actor_batch * 2),
        )
        fault_plan = FaultPlan.random(
            args.chaos,
            actors=actor_cores * threads_per_core,
            horizon=horizon,
            crash_rate=2.0 / horizon,
            slow_rate=4.0 / horizon,
        )
        print(f"chaos seed {args.chaos}: {len(fault_plan.events)} "
              "scheduled faults")
        chaos_kwargs = dict(stall_timeout=5.0, restart_backoff=0.1)

    net = ConvActorCritic(Pong.num_actions, channels=(16, 32), blocks=1)
    seb = Sebulba(
        device_env=scenarios,
        network=net,
        optimizer=optim.rmsprop(3e-4, clip_norm=1.0),
        config=SebulbaConfig(
            num_actor_cores=actor_cores,
            threads_per_actor_core=threads_per_core,
            actor_batch_size=actor_batch,
            trajectory_length=args.trajectory,
            **chaos_kwargs,
        ),
        fault_plan=fault_plan,
    )
    out = seb.fit(jax.random.key(0), total_frames=args.frames, log_every=25)
    print(
        f"\n{out['frames']:,} frames in {out['seconds']:.1f}s "
        f"-> {out['fps']:,.0f} FPS, {out['updates']} updates, "
        f"mean return {out['mean_return']:.2f}"
    )
    if args.chaos is not None:
        print(
            f"chaos: {out['actor_restarts']} restarts, "
            f"{out['watchdog_stalls']} watchdog stalls, "
            f"{out['actor_quarantined']} quarantined"
        )
    for name, c in out["scenarios"].items():
        print(f"  {name:>9}: weight {c['weight']:.1f}, rows {c['rows']}, "
              f"episodes {c['episodes']:,}, "
              f"mean return {c['mean_return']:.2f}")


if __name__ == "__main__":
    main()
