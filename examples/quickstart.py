"""Quickstart: Anakin on Catch — the paper's Colab demo, reproduced.

The whole agent-environment loop (env stepping, action selection, A2C
update) compiles into ONE XLA program, replicated over every available
device with explicit pmean gradient averaging (paper Fig. 2), driven
through the unified Podracer runner surface (``repro.api``): one ``fit``
call, one result schema, optional ``param_version``-stamped checkpoints.

    PYTHONPATH=src python examples/quickstart.py
"""

import argparse

import jax

from repro import optim
from repro.agents.actor_critic import MLPActorCritic
from repro.compile_cache import enable_compile_cache
from repro.core.anakin import Anakin, AnakinConfig
from repro.envs import Catch

FULL_FRAMES = 320_000  # 10 compiled calls at the default config


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=FULL_FRAMES)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist param_version-stamped checkpoints here")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint every N learner updates (0 = only the "
                         "final save when --checkpoint-dir is set)")
    args = ap.parse_args()
    enable_compile_cache()

    env = Catch()
    net = MLPActorCritic(env.num_actions, hidden=(64, 64))
    anakin = Anakin(
        env,
        net,
        optim.adam(3e-3, clip_norm=1.0),
        AnakinConfig(
            unroll_length=10,  # N env steps per update
            batch_per_device=64,  # vmap width (fill the core)
            iterations_per_call=50,  # updates fused into one XLA call
            mode="shard_map",  # paper-faithful explicit pmean
        ),
    )
    print(f"devices: {jax.device_count()}  "
          f"global env batch: {anakin.global_batch}")

    out = anakin.fit(
        jax.random.key(0), total_frames=args.frames, log_every=50,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
    )
    reward = float(out["metrics"].get("reward", float("nan")))
    print(
        f"\n{out['frames']:,} frames in {out['seconds']:.1f}s "
        f"-> {out['fps']:,.0f} FPS, {out['updates']} updates, "
        f"final reward/step {reward:+.3f} (optimal = +{1 / 9:.3f})"
    )
    if args.frames >= FULL_FRAMES:  # smoke runs train too little to judge
        assert reward > 0.08, "did not learn Catch"


if __name__ == "__main__":
    main()
