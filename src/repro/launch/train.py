"""Training launcher: run the Sebulba-learner train_step for any assigned
architecture on the local mesh (reduced config by default — the full configs
are exercised via the dry-run on the production mesh).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch deepseek-moe-16b \
        --moe-impl a2a --steps 20   # needs >1 device for the model axis
"""

from __future__ import annotations

import argparse
import time

import jax

from repro import optim
from repro.checkpoint import save
from repro.compile_cache import enable_compile_cache
from repro.configs.base import ALIASES, get_config, get_reduced_config
from repro.launch.specs import make_batch
from repro.launch.steps import TrainHParams, make_train_step
from repro.models import make_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, help=f"one of {sorted(ALIASES)}")
    ap.add_argument("--full", action="store_true",
                    help="full config (needs real accelerators)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--moe-impl", default="sort")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_reduced_config(args.arch)
    mesh = None
    if args.moe_impl == "a2a":
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
    model = make_model(cfg, moe_impl=args.moe_impl, mesh=mesh)
    params = model.init(jax.random.key(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {n / 1e6:.1f}M params on {jax.device_count()} device(s)")

    opt = optim.adam(
        optim.warmup_cosine(args.lr, warmup=10, total_steps=args.steps),
        clip_norm=1.0,
    )
    step = jax.jit(make_train_step(model, opt, TrainHParams()))
    opt_state = opt.init(params)
    t0 = time.time()
    for i in range(args.steps):
        batch = make_batch(cfg, args.batch, args.seq, rng=jax.random.key(i))
        params, opt_state, metrics = step(params, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            tps = args.batch * args.seq * (i + 1) / (time.time() - t0)
            print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                  f"ce {float(metrics['ce']):.4f}  tok/s {tps:,.0f}")
    if args.ckpt:
        save(args.ckpt, params)
        print(f"checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
