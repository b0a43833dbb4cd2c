"""End-to-end driver: an LM policy as a first-class Podracer agent.

The transformer *is* the policy: ``LMPolicyAgent.act`` generates one token
per env step through ``model.decode_step`` (the flash_decode hot loop),
threading the KV cache + position counter as Sebulba's declared carry, on
the pure-JAX ``TokenEnv`` copy/reverse task.  The learner re-scores stale
generations with one teacher-forced forward and optimizes the V-trace-
corrected LM objective (CE + importance-weighted actor-critic).  All of it
flows through the UNCHANGED Sebulba core — ring, drain, shard, publish —
and reports the unified ``repro.api.RESULT_KEYS`` schema.

Default config is a ~25M-parameter qwen2-family model sized for this CPU
container; ``--preset 100m`` scales to ~100M params (the assignment's
end-to-end target — run it on real hardware or be patient); ``--preset
tiny`` is the CI smoke size.

    XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        PYTHONPATH=src python examples/train_lm_rl.py --preset 25m
"""

import argparse
import dataclasses

import jax

from repro import optim
from repro.agents.lm_policy import LMPolicyAgent, LMReplayPolicyAgent
from repro.checkpoint import save
from repro.compile_cache import enable_compile_cache
from repro.configs.base import ReplayConfig, get_config
from repro.core.sebulba import Sebulba, SebulbaConfig
from repro.envs import TokenEnv
from repro.launch.steps import TrainHParams

PRESETS = {
    # CI smoke size: compiles in seconds
    "tiny": dict(num_layers=2, d_model=64, num_heads=2, num_kv_heads=1,
                 head_dim=32, d_ff=128, vocab_size=128),
    # ~25M params: CPU-friendly
    "25m": dict(num_layers=4, d_model=384, num_heads=6, num_kv_heads=2,
                head_dim=64, d_ff=1536, vocab_size=8192),
    # ~100M params: the assignment's end-to-end scale
    "100m": dict(num_layers=8, d_model=768, num_heads=12, num_kv_heads=4,
                 head_dim=64, d_ff=3072, vocab_size=16384),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="25m", choices=sorted(PRESETS))
    ap.add_argument("--frames", type=int, default=4096)
    ap.add_argument("--task", default="copy", choices=["copy", "reverse"])
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--data-vocab", type=int, default=16,
                    help="distinct prompt tokens (small -> learnable fast)")
    ap.add_argument("--actor-cores", type=int, default=1)
    ap.add_argument("--actor-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--replay", action="store_true",
                    help="train off-policy with prioritized replay "
                         "(the declared replay capability)")
    ap.add_argument("--ckpt", default="",
                    help="save the trained params here (default: no save)")
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

    cfg = dataclasses.replace(
        get_config("qwen2-1.5b"), **PRESETS[args.preset], qkv_bias=True,
        remat="none",
    )
    env = TokenEnv(vocab_size=cfg.vocab_size, prompt_len=args.prompt_len,
                   task=args.task, data_vocab=args.data_vocab)
    agent = (LMReplayPolicyAgent if args.replay else LMPolicyAgent)(
        cfg, max_seq=env.episode_len,
        hparams=TrainHParams(rl_weight=0.1, entropy_cost=0.003),
    )
    n_params = sum(x.size for x in jax.tree.leaves(
        agent.init(jax.random.key(0), env.obs_shape)))
    print(f"model: {n_params / 1e6:.1f}M params "
          f"({cfg.num_layers}L d={cfg.d_model}), "
          f"{args.task} task, episode {env.episode_len} tokens")

    seb = Sebulba(
        optimizer=optim.adam(args.lr, clip_norm=1.0),
        config=SebulbaConfig(
            num_actor_cores=actor_cores,
            threads_per_actor_core=2,
            actor_batch_size=actor_batch,
            trajectory_length=env.episode_len,
            replay=ReplayConfig(capacity=256, sample_batch_size=actor_batch,
                                min_size=4 * actor_batch, prioritized=True)
            if args.replay else None,
        ),
        agent=agent,
        device_env=env,
    )
    out = seb.fit(jax.random.key(0), total_frames=args.frames, log_every=25)
    m = out["metrics"]
    print(
        f"\n{out['frames']:,} frames in {out['seconds']:.1f}s "
        f"-> {out['fps']:,.0f} FPS, {out['updates']} updates\n"
        f"loss {float(m['loss']):.4f}  ce {float(m['ce']):.4f}  "
        f"rl {float(m['rl']):+.4f}  entropy {float(m['entropy']):.3f}  "
        f"mean return {out['mean_return']:.2f} "
        f"(max {env.episode_len // 2})"
    )
    if args.replay:
        print(f"replay: {out['replay_size']} trajectories held")
    if args.ckpt:
        save(args.ckpt, out["params"])
        print(f"checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
