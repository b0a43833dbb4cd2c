"""Serve a small model with continuous batching: the PR 10 serving stack
(paged KV cache + chunked prefill + request scheduler) driven end to end
through the public API, with the static-batch path alongside for
comparison.

Prefill goes through the fused ``Model.prefill_step`` forward pass — one
``(B, C)`` dispatch per chunk — not the old token-by-token teacher-forced
decode loop (the prefill-vs-decode parity pin in tests/test_models.py
covers their equivalence).

    PYTHONPATH=src python examples/serve_lm.py --batch 8 --prompt-len 64 --gen 64
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs.base import get_reduced_config
from repro.launch.steps import make_serve_step
from repro.models import make_model
from repro.serve import Request, ServeConfig, ServeEngine


def static_batch(model, params, prompts, gen: int):
    """The pre-engine baseline: fused prefill of the whole (equal-length)
    prompt batch, then lockstep greedy decode — the batch moves at the
    pace of its slowest request."""
    cfg = model.cfg
    B, L = prompts.shape
    total = L + gen
    cache, _ = model.init_cache(B, total)
    prefill = jax.jit(model.prefill_step)
    serve = jax.jit(make_serve_step(model))

    t0 = time.time()
    logits, _, cache = prefill(
        params, cache, prompts, jnp.zeros((B,), jnp.int32)
    )
    logits[:, -1].block_until_ready()
    prefill_s = time.time() - t0

    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    generated = [tok]
    t0 = time.time()
    for t in range(L, total - 1):
        tok, cache = serve(params, cache, tok, jnp.int32(t))
        generated.append(tok)
    out = jnp.concatenate(generated, axis=1)
    out.block_until_ready()
    decode_s = time.time() - t0
    return out, prefill_s, decode_s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced_config(args.arch)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    B = args.batch
    total = args.prompt_len + args.gen
    print(f"serving reduced {cfg.name}: batch {B}, cache {total} tokens")

    prompts = jax.random.randint(
        jax.random.key(1), (B, args.prompt_len), 0, cfg.vocab_size,
        dtype=jnp.int32,
    )

    # --- static batching: fused prefill + lockstep decode ---------------
    out, prefill_s, decode_s = static_batch(model, params, prompts, args.gen)
    print(f"static prefill (fused): {B * args.prompt_len / prefill_s:,.0f} tok/s")
    print(f"static decode:          {B * args.gen / decode_s:,.0f} tok/s")
    print(f"sample continuation (request 0): {out[0, :16].tolist()}")

    # --- continuous batching: paged KV + chunked prefill ----------------
    bs = 16
    scfg = ServeConfig(
        batch_rows=B, prefill_chunk=32, token_budget=B + 32,
        block_size=bs, num_blocks=1 + B * (total // bs + 1),
        max_seq=((total + bs - 1) // bs) * bs,
        temperature=args.temperature, seed=0,
    )
    engine = ServeEngine(model, params, scfg, paged=True)
    reqs = [
        Request(rid=i + 1, prompt=tuple(int(t) for t in prompts[i]),
                max_new_tokens=args.gen)
        for i in range(B)
    ]
    res = engine.run(reqs)
    print(f"continuous (paged KV):  {res['tokens_per_s']:,.0f} tok/s processed, "
          f"TTFT p50 {res['ttft_p50'] * 1e3:.1f} ms, "
          f"occupancy peak {res['cache_occupancy_peak']:.0%}")
    print(f"sample continuation (request 1): {res['outputs'][1][:16]}")


if __name__ == "__main__":
    main()
