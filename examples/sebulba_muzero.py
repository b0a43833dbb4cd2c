"""Sebulba running MuZero with pure-JAX MCTS on the actor cores (paper
§Sebulba / Fig. 4c), including the batch-splitting trick that decouples
acting batch size from learning batch size (learner_microbatches).

Protocol notes (repro.api): ``MuZeroAgent`` declares
``AgentSpec(extras_keys=("visit_probs",))`` — the per-step MCTS visit
distributions ride the device trajectory ring as a NAMED extra
(``Trajectory.extras["visit_probs"]``), validated against the declaration
when the ring is allocated.  That named channel is exactly what the
roadmap's MuZero-reanalyze needs to read back out of replay (sample a
trajectory, re-run MCTS under fresh params, overwrite ``visit_probs``) —
a reanalyze agent would declare ``AgentSpec(replay=True,
extras_keys=("visit_probs",))`` and plug into Sebulba replay mode
unchanged; this example is the on-policy template for it.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/sebulba_muzero.py --frames 10000
"""

import argparse

import jax

from repro import optim
from repro.agents.muzero import MuZeroAgent, MuZeroConfig
from repro.compile_cache import enable_compile_cache
from repro.core.sebulba import Sebulba, SebulbaConfig
from repro.envs import BatchedHostEnv, HostPong


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=10_000)
    ap.add_argument("--simulations", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--actor-batch", type=int, default=16)
    ap.add_argument("--trajectory", type=int, default=12)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist param_version-stamped checkpoints here")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint every N learner updates")
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    actor_cores = min(2, n_dev - 1) if n_dev > 1 else 1
    learners = max(n_dev - actor_cores, 1)
    # the batch shards across learner cores AND splits into microbatches
    mult = learners * args.microbatches
    actor_batch = -(-args.actor_batch // mult) * mult
    if actor_batch != args.actor_batch:
        print(f"actor batch {args.actor_batch} -> {actor_batch} "
              f"(multiple of {learners} learners x {args.microbatches} "
              "microbatches)")

    agent = MuZeroAgent(
        HostPong.num_actions,
        MuZeroConfig(num_simulations=args.simulations, max_depth=6,
                     unroll_steps=4),
    )
    seb = Sebulba(
        env_factory=lambda seed: HostPong(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        optimizer=optim.adam(1e-3, clip_norm=1.0),
        agent=agent,
        config=SebulbaConfig(
            num_actor_cores=actor_cores,
            actor_batch_size=actor_batch,
            trajectory_length=args.trajectory,
            learner_microbatches=args.microbatches,  # the paper's trick
        ),
    )
    out = seb.fit(jax.random.key(0), total_frames=args.frames, log_every=10,
                  checkpoint_dir=args.checkpoint_dir,
                  checkpoint_every=args.checkpoint_every)
    print(
        f"\n{out['frames']:,} frames, {out['fps']:,.0f} FPS "
        f"(search-based acting), mean return {out['mean_return']:.2f}"
    )


if __name__ == "__main__":
    main()
