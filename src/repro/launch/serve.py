"""Serving launcher: continuous-batching ServeEngine (dense/moe
attention families) or the static batched decode loop (everything else —
ssm/hybrid recurrent state has no paged layout).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --full

The path is chosen from the model before anything runs
(``engine_refusal``); an engine failure is an error, never a fallback.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ALIASES, get_config, get_reduced_config
from repro.launch.steps import make_serve_step
from repro.models import make_model
from repro.serve import Request, ServeConfig, ServeEngine
from repro.serve.engine import engine_refusal


def _serve_static(model, params, args) -> None:
    """The pre-engine path: one fixed batch, lockstep greedy decode."""
    cache, _ = model.init_cache(args.batch, args.cache_len)
    serve = jax.jit(make_serve_step(model))
    tok = jnp.ones((args.batch, 1), jnp.int32)
    tok, cache = serve(params, cache, tok, jnp.int32(0))  # compile
    t0 = time.time()
    toks = [tok]
    for t in range(1, args.gen):
        tok, cache = serve(params, cache, tok, jnp.int32(t))
        toks.append(tok)
    dt = time.time() - t0
    out = jnp.concatenate(toks, axis=1)
    print(f"{model.cfg.name}: {args.batch} streams x {args.gen} tokens "
          f"(static batch), "
          f"{args.batch * (args.gen - 1) / dt:,.0f} tok/s steady-state")
    print("stream 0:", out[0, :16].tolist())


def _serve_engine(model, params, args) -> None:
    cfg = model.cfg
    scfg = ServeConfig(
        batch_rows=args.batch,
        prefill_chunk=16,
        token_budget=args.batch + 16,
        block_size=16,
        num_blocks=1 + args.batch * (args.cache_len // 16),
        max_seq=args.cache_len,
        temperature=args.temperature,
        top_k=args.top_k,
        seed=0,
    )
    engine = ServeEngine(model, params, scfg, paged=True)
    prompts = jax.random.randint(
        jax.random.key(1), (2 * args.batch, 8), 0, cfg.vocab_size
    )
    reqs = [
        Request(rid=i + 1, prompt=tuple(int(t) for t in prompts[i]),
                max_new_tokens=args.gen)
        for i in range(2 * args.batch)
    ]
    res = engine.run(reqs)
    print(f"{cfg.name}: {res['completed']} requests x {args.gen} tokens "
          f"(continuous batching, paged KV), "
          f"{res['tokens_per_s']:,.0f} tok/s processed, "
          f"TTFT p50 {res['ttft_p50'] * 1e3:.1f} ms, "
          f"cache occupancy peak {res['cache_occupancy_peak']:.0%}")
    print("request 1:", res["outputs"][1][:16])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, help=f"one of {sorted(ALIASES)}")
    ap.add_argument("--full", action="store_true",
                    help="published widths and depth (needs an accelerator)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_reduced_config(args.arch)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    reason = engine_refusal(model)
    if reason is None:
        _serve_engine(model, params, args)
    else:
        print(f"[serve] static batching: {reason}")
        _serve_static(model, params, args)


if __name__ == "__main__":
    main()
