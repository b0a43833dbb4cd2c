"""Sebulba running IMPALA/V-trace on host (CPU) environments — paper Fig. 3.

Run with several placeholder devices to exercise the actor/learner core
split (on a real TPU host the 8 cores appear automatically):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/sebulba_impala.py --frames 50000
"""

import argparse

import jax

from repro import optim
from repro.agents.impala import ConvActorCritic
from repro.compile_cache import enable_compile_cache
from repro.core.sebulba import Sebulba, SebulbaConfig
from repro.envs import BatchedHostEnv, HostPong, Pong


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=50_000)
    ap.add_argument("--actor-cores", type=int, default=2)
    ap.add_argument("--actor-batch", type=int, default=32)
    ap.add_argument("--trajectory", type=int, default=20)
    ap.add_argument("--device-envs", action="store_true",
                    help="step the pure-JAX Pong twin on device (fused "
                         "env+act actor step) instead of the host env pool")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist param_version-stamped checkpoints here "
                         "(the runner owns persistence — see repro.api)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint every N learner updates")
    ap.add_argument("--restore-from", default=None,
                    help="warm-start params from a checkpoint file or dir")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="inject a seeded FaultPlan (random crashes/hangs/"
                         "stragglers across the actor fleet) to exercise "
                         "supervision: restarts, watchdog, quarantine. Same "
                         "seed, same schedule.")
    ap.add_argument("--hosts", type=int, default=1, metavar="N",
                    help="run as one host of an N-host elastic fleet "
                         "(N-1 simulated peers renew leases in a shared "
                         "registry dir; with --chaos, seeded host_crash/"
                         "host_rejoin events hit the peers mid-run and the "
                         "learner reshards on each epoch bump)")
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    actor_cores = min(args.actor_cores, max(1, n_dev - 1)) if n_dev > 1 else 1
    learners = max(n_dev - actor_cores, 1)
    # the batch shards across learner cores; round up to the next multiple
    # (a 6-learner split would otherwise reject the power-of-two default)
    actor_batch = -(-args.actor_batch // learners) * learners
    if actor_batch != args.actor_batch:
        print(f"actor batch {args.actor_batch} -> {actor_batch} "
              f"(multiple of {learners} learners)")
    print(f"devices: {n_dev} -> {actor_cores} actor / "
          f"{learners} learner cores")

    net = ConvActorCritic(HostPong.num_actions, channels=(16, 32), blocks=1)
    env_kwargs = (
        {"device_env": Pong}
        if args.device_envs
        else {
            "env_factory": lambda seed: HostPong(seed=seed),
            "make_batched_env": lambda f, n: BatchedHostEnv(f, n),
        }
    )
    threads_per_core = 2
    peer_ids = tuple(f"peer{i}" for i in range(args.hosts - 1))
    fault_plan = None
    chaos_kwargs = {}
    if args.chaos is not None:
        from repro.fault import FaultPlan

        # per-slot steps ~ frames / (slots * batch); schedule over the
        # first half so recoveries happen while there is run left to show
        horizon = max(
            20,
            args.frames // (actor_cores * threads_per_core * actor_batch * 2),
        )
        fault_plan = FaultPlan.random(
            args.chaos,
            actors=actor_cores * threads_per_core,
            horizon=horizon,
            crash_rate=2.0 / horizon,   # ~2 crashes per slot
            hang_rate=0.5 / horizon,    # ~1 hang across a 2-slot fleet
            slow_rate=4.0 / horizon,
            # host chaos (the elastic tier): expect ~1 loss per peer over
            # the window, rejoining a quarter-window later.  Host steps
            # count LEARNER updates, which run on a comparable scale.
            peer_hosts=peer_ids,
            host_crash_rate=3.0 / horizon,
            host_rejoin_after=max(2, horizon // 4),
        )
        print(f"chaos seed {args.chaos}: {len(fault_plan.events)} "
              "scheduled faults")
        # a tight (but compile-safe: startup is grace-period exempt) stall
        # budget so injected hangs are caught within the demo run
        chaos_kwargs = dict(stall_timeout=5.0, restart_backoff=0.1)
    cluster = None
    if args.hosts > 1:
        import tempfile

        from repro.distributed import HostSupervisor

        registry_dir = tempfile.mkdtemp(prefix="sebulba_registry_")
        cluster = HostSupervisor(
            registry_dir, "host0", ttl=0.3, peers=peer_ids,
            fault_plan=fault_plan, checkpoint_dir=args.checkpoint_dir,
        )
        print(f"elastic fleet: host0 + {len(peer_ids)} peers, "
              f"registry {registry_dir}")
    seb = Sebulba(
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
        cluster=cluster,
        **env_kwargs,
    )
    out = seb.fit(jax.random.key(0), total_frames=args.frames, log_every=25,
                  checkpoint_dir=args.checkpoint_dir,
                  checkpoint_every=args.checkpoint_every,
                  restore_from=args.restore_from)
    print(
        f"\n{out['frames']:,} frames in {out['seconds']:.1f}s "
        f"-> {out['fps']:,.0f} FPS, {out['updates']} updates, "
        f"mean return {out['mean_return']:.2f}, "
        f"{out['checkpoints_saved']} checkpoints"
    )
    if args.chaos is not None:
        print(
            f"chaos: {out['actor_restarts']} restarts, "
            f"{out['watchdog_stalls']} watchdog stalls, "
            f"{out['actor_quarantined']} quarantined"
        )
    if args.hosts > 1:
        print(
            f"hosts: epoch {out['epoch']}, {out['hosts_lost']} lost, "
            f"{out['hosts_joined']} joined, {out['reshards']} reshards, "
            f"{seb.stale_epoch_trajs} stale-epoch trajectories dropped"
        )


if __name__ == "__main__":
    main()
