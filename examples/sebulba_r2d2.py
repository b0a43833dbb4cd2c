"""R2D2 on Sebulba: recurrent agent, stored state, burn-in, prioritized
sequence replay (Kapturowski et al. 2019) — end to end.

This *is* R2D2 now, not just its dataflow: the agent is a recurrent
actor-critic (IMPALA conv torso -> RG-LRU temporal core, the ``rglru_scan``
kernel wrapper — stored-state training scans take the log-depth
associative scan with its linear-memory custom VJP on every backend, and
acting is a single-step recurrence; the zero-state-only Pallas TPU kernel
serves griffin's prefill, not this agent.  ``--core lax`` swaps in the
sequential pure-lax reference).  Actor cores thread the recurrent state
through the fused donated act-step (reset on episode boundaries via the
discount channel) and record the state entering each trajectory slice; the
slice replays from that **stored state**, and a ``--burn-in`` prefix is
unrolled gradient-free to refresh it before the V-trace loss.

Stored state vs zero state vs burn-in (the Kapturowski et al. ablation):

  * **zero-state** replay (their baseline) zeroes the carry at the start of
    every replayed sequence — cheap, but the early steps of every sequence
    train against a state distribution the actor never produces;
  * **stored state** replays from the actor's recorded carry (what this
    example always does) — right distribution, but *stale*: it was computed
    under the params of record time, not the params doing the update;
  * **burn-in** (``--burn-in K``) repairs the staleness by re-unrolling the
    first K steps with CURRENT params from the stored state, gradient-free,
    so only the refreshed suffix trains.  Their best results combine
    stored state + burn-in, which is the configuration here.

The learner side is unchanged Podracer machinery: trajectory shards stream
device-to-device into the replay ring sharded over the learner mesh, every
update trains on a mixed online+replay batch inside one fused donated jit
(insert -> sample -> burn-in -> weighted V-trace -> TD-priority
write-back), and V-trace absorbs the policy lag.  See ARCHITECTURE.md for
the full dataflow.

Run with placeholder devices to exercise the full actor/learner/replay
split (real TPU hosts expose their 8 cores automatically):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/sebulba_r2d2.py --frames 50000
"""

import argparse

import jax

from repro import optim
from repro.agents.recurrent import (
    RecurrentConvActorCritic,
    RecurrentReplayImpalaAgent,
)
from repro.compile_cache import enable_compile_cache
from repro.configs.base import ReplayConfig
from repro.core.sebulba import Sebulba, SebulbaConfig
from repro.envs import BatchedHostEnv, HostPong


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=50_000)
    ap.add_argument("--actor-cores", type=int, default=2)
    ap.add_argument("--actor-batch", type=int, default=24)
    ap.add_argument("--trajectory", type=int, default=20)
    ap.add_argument("--burn-in", type=int, default=5,
                    help="gradient-free unroll steps refreshing the stored "
                         "state before the V-trace loss (0 disables; must "
                         "be < --trajectory)")
    ap.add_argument("--core", choices=["rglru", "lax"], default="rglru",
                    help="temporal core: the rglru_scan kernel wrapper "
                         "(log-depth associative scan + linear-memory "
                         "custom VJP for these stored-state scans) or the "
                         "sequential pure-lax reference")
    ap.add_argument("--rnn-width", type=int, default=128,
                    help="RG-LRU state width (the stored-state bytes per "
                         "sequence scale with this)")
    ap.add_argument("--capacity", type=int, default=2048,
                    help="replay slots (global, sharded over learner cores)")
    ap.add_argument("--replay-batch", type=int, default=24,
                    help="replay trajectories sampled per learner update")
    ap.add_argument("--min-size", type=int, default=96,
                    help="warmup inserts before learning starts")
    ap.add_argument("--uniform", action="store_true",
                    help="uniform instead of prioritized sampling")
    ap.add_argument("--anneal-updates", type=int, default=0,
                    help="linearly anneal the PER importance exponent "
                         "(beta) to 1.0 over this many learner updates "
                         "(0 keeps it fixed)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist param_version-stamped checkpoints here")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint every N learner updates")
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    actor_cores = min(args.actor_cores, max(1, n_dev - 1)) if n_dev > 1 else 1
    learners = max(n_dev - actor_cores, 1)

    # Sebulba shards the batch and the replay ring over the learner cores,
    # so round the requested sizes up to the nearest multiple of that count
    # (the CLI defaults assume powers of two; a 6-learner split would
    # otherwise be rejected).
    def _round_up(x: int, m: int) -> int:
        return -(-x // m) * m

    actor_batch = _round_up(args.actor_batch, learners)
    capacity = _round_up(args.capacity, learners)
    replay_batch = _round_up(args.replay_batch, learners)
    if (actor_batch, capacity, replay_batch) != (
            args.actor_batch, args.capacity, args.replay_batch):
        print(f"rounded to learner multiple of {learners}: "
              f"actor_batch={actor_batch} capacity={capacity} "
              f"replay_batch={replay_batch}")
    print(f"devices: {n_dev} -> {actor_cores} actor / {learners} learner "
          f"cores, replay ring {capacity} slots "
          f"({capacity // learners}/core), burn-in {args.burn_in}, "
          f"core {args.core}")

    net = RecurrentConvActorCritic(
        HostPong.num_actions, channels=(16, 32), blocks=1,
        rnn_width=args.rnn_width, core=args.core,
    )
    config = SebulbaConfig(
        num_actor_cores=actor_cores,
        threads_per_actor_core=2,
        actor_batch_size=actor_batch,
        trajectory_length=args.trajectory,
        burn_in=args.burn_in,
        replay=ReplayConfig(
            capacity=capacity,
            sample_batch_size=replay_batch,
            min_size=min(args.min_size, capacity),
            prioritized=not args.uniform,
            importance_anneal_updates=args.anneal_updates,
        ),
    )
    seb = Sebulba(
        env_factory=lambda seed: HostPong(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        optimizer=optim.rmsprop(3e-4, clip_norm=1.0),
        config=config,
        agent=RecurrentReplayImpalaAgent(net, config),
    )
    out = seb.fit(jax.random.key(0), total_frames=args.frames, log_every=25,
                  checkpoint_dir=args.checkpoint_dir,
                  checkpoint_every=args.checkpoint_every)
    print(
        f"\n{out['frames']:,} frames in {out['seconds']:.1f}s "
        f"-> {out['fps']:,.0f} FPS, {out['updates']} updates, "
        f"replay size {out['replay_size']}, "
        f"mean return {out['mean_return']:.2f}"
    )


if __name__ == "__main__":
    main()
