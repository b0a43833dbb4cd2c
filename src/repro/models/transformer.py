"""Decoder stacks for the dense / MoE / hybrid / VLM / audio families.

Layer layout strategy (compile-time-friendly, see DESIGN.md §5):
  * uniform-pattern archs (llama3-405b, qwen2, qwen3, granite-moe, mamba2,
    whisper, deepseek's 27 MoE layers) use **scan-over-layers** with stacked
    parameters (leading logical axis "layers") — one traced layer body
    regardless of depth, which keeps the 126-layer 405B HLO small;
  * heterogeneous patterns (gemma3 local:global, griffin RRA, VLM cross-attn
    inserts) use a Python loop — their pattern scalars (window size, rope
    theta) must be static per layer.

Every layer body is optionally wrapped in jax.checkpoint (cfg.remat).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.flash_decode.ops import flash_decode
from repro.models import attention as attn
from repro.models import griffin, layers, mamba2, moe as moe_lib
from repro.param import ParamBuilder

Params = Any


# ---------------------------------------------------------------------------
# Stacked-parameter helpers (scan-over-layers)
# ---------------------------------------------------------------------------


def init_stacked(
    b: ParamBuilder, name: str, n: int, init_one: Callable[[ParamBuilder], None]
) -> None:
    """Initialize ``n`` copies of a layer and stack along a "layers" dim."""
    for i in range(n):
        with b.scope(f"__tmp_{name}_{i}"):
            init_one(b)
    # stack: pull the temp subtrees out and stack leaves
    params_root = b._subdict(b._params)
    axes_root = b._subdict(b._axes)
    stacked_p = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[params_root.pop(f"__tmp_{name}_{i}") for i in range(n)],
    )
    axes_trees = [axes_root.pop(f"__tmp_{name}_{i}") for i in range(n)]
    stacked_a = jax.tree.map(
        lambda a, *_: ("layers",) + a,
        axes_trees[0],
        *axes_trees[1:],
        is_leaf=lambda x: isinstance(x, tuple),
    )
    params_root[name] = stacked_p
    axes_root[name] = stacked_a


def scan_layers(
    stacked: Params,
    x: jax.Array,
    layer_fn: Callable,
    *,
    remat: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Run x through scanned layers.  layer_fn(p, x) -> (x, aux_scalar)."""
    f = jax.checkpoint(layer_fn) if remat else layer_fn

    def body(carry, p):
        x, aux = carry
        x, a = f(p, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stacked)
    return x, aux


def scan_decode_layers(
    stacked: Params,
    cache: Params,
    x: jax.Array,
    step_fn: Callable,
) -> tuple[jax.Array, Params]:
    """Decode step through scanned layers, threading per-layer cache.

    step_fn(p, cache_layer, x) -> (x, new_cache_layer).
    """

    def body(x, inp):
        p, c = inp
        x, c2 = step_fn(p, c, x)
        return x, c2

    x, new_cache = jax.lax.scan(body, x, (stacked, cache))
    return x, new_cache


# ---------------------------------------------------------------------------
# Sublayers
# ---------------------------------------------------------------------------


def residual(x: jax.Array, branch: jax.Array, cfg: ArchConfig) -> jax.Array:
    """x + the branch, the branch scaled by ``cfg.residual_multiplier``
    where that is not 1 (granite)."""
    if cfg.residual_multiplier != 1.0:
        branch = branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)
    return x + branch


def scale_queries(q: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Queries scaled so that the attention paths' 1/sqrt(head_dim) gives
    the configured softmax scale ``cfg.attention_multiplier`` (granite:
    1/64 at head width 64, so q/8, exact in bfloat16)."""
    if not cfg.attention_multiplier:
        return q
    s = cfg.attention_multiplier * cfg.head_dim**0.5
    return q * jnp.asarray(s, q.dtype)


def init_attn_layer(b: ParamBuilder, cfg: ArchConfig) -> None:
    dims = attn.AttnDims(cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    layers.init_rms_norm(b, "attn_norm", cfg.d_model)
    attn.init_attention(b, "attn", dims, qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)


def attn_sublayer(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    window: int = 0,
    theta: float | None = None,
    causal: bool = True,
) -> jax.Array:
    h = layers.rms_norm(p["attn_norm"], x, cfg.rms_norm_eps)
    rope_pos = positions if cfg.pos_embed == "rope" else None
    q, k, v = attn.qkv_project(
        p["attn"], h, positions=rope_pos,
        rope_theta=theta if theta is not None else cfg.rope_theta,
        eps=cfg.rms_norm_eps,
    )
    q = scale_queries(q, cfg)
    if window:
        # blocked local attention materializes O(T·2W) probabilities;
        # checkpoint so they are recomputed (transiently) in backward
        f = jax.checkpoint(
            lambda q, k, v: attn.sliding_window_attention(
                q, k, v, window=window, softcap=cfg.attn_logit_softcap
            )
        )
        out = f(q, k, v)
    else:
        # full_attention carries a flash-attention custom VJP: backward
        # recomputes probabilities per KV chunk from (q, k, lse) — the
        # O(T·S) scan residuals this replaces were the dominant training
        # memory term (§Perf, qwen2 train_4k)
        out = attn.full_attention(
            q, k, v, causal=causal, softcap=cfg.attn_logit_softcap
        )
    return residual(x, attn.output_project(p["attn"], out), cfg)


def _rope_positions(pos: jax.Array | None, width: int = 1) -> jax.Array | None:
    """Decode-time rope positions: scalar pos -> (1, width) lockstep row;
    per-row (B,) pos -> (B, width), row b at pos[b]..pos[b]+width-1."""
    if pos is None:
        return None
    pos = jnp.asarray(pos)
    base = pos[None, None] if pos.ndim == 0 else pos[:, None]
    return base + jnp.arange(width)[None, :] if width > 1 else base


def attn_sublayer_decode(
    p: Params,
    cache: dict,
    x: jax.Array,
    pos: jax.Array,
    cfg: ArchConfig,
    *,
    window: int = 0,
    theta: float | None = None,
    block_tables: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """One-token decode.  cache: {"k": (B,S,K,h), "v": ...} dense, or
    {"k": (P,bs,K,h), "v": ...} page pools when ``block_tables`` is given.
    ``pos`` is a scalar (lockstep, the PR 9 path — unchanged) or a (B,)
    vector of per-row positions (the serving path)."""
    h = layers.rms_norm(p["attn_norm"], x, cfg.rms_norm_eps)
    positions = _rope_positions(pos) if cfg.pos_embed == "rope" else None
    q, k, v = attn.qkv_project(
        p["attn"], h, positions=positions,
        rope_theta=theta if theta is not None else cfg.rope_theta,
        eps=cfg.rms_norm_eps,
    )
    q = scale_queries(q, cfg)
    if block_tables is not None:
        if window or cfg.attn_logit_softcap:
            raise ValueError(
                "paged decode supports global attention without logit "
                "softcap only; sliding-window / softcap layers keep the "
                "dense cache"
            )
        kc, vc = attn.update_paged_kv_cache(
            cache["k"], cache["v"], k, v, block_tables, pos
        )
        out = flash_decode(q, kc, vc, pos, block_tables=block_tables)
        return residual(x, attn.output_project(p["attn"], out), cfg), {
            "k": kc, "v": vc}
    S = cache["k"].shape[1]
    if window and S == window:
        kc, vc = griffin.ring_cache_update(cache["k"], cache["v"], k, v, pos, window)
        out = griffin.ring_decode_attention(q, kc, vc, pos, window)
    else:
        kc, vc = attn.update_kv_cache(cache["k"], cache["v"], k, v, pos)
        if cfg.attn_logit_softcap:
            # softcapped logits (gemma3) stay on the jnp oracle — the
            # Pallas decode kernel has no softcap path
            out = attn.decode_attention(
                q, kc, vc, pos, window=window, softcap=cfg.attn_logit_softcap
            )
        else:
            # the decode hot loop: Pallas flash_decode on TPU, its
            # bit-identical jnp oracle elsewhere (kernels/flash_decode)
            out = flash_decode(q, kc, vc, pos, window=window)
    return residual(x, attn.output_project(p["attn"], out), cfg), {
        "k": kc, "v": vc}


def attn_sublayer_prefill(
    p: Params,
    cache: dict,
    x: jax.Array,
    pos: jax.Array,
    cfg: ArchConfig,
    *,
    block_tables: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Chunked prefill: a (B, C, D) token chunk whose row-b tokens sit at
    positions pos[b]..pos[b]+C-1.  The chunk's K/V is written into the
    cache first, then the chunk attends to the whole cache with the
    per-row position mask — within-chunk causality falls out of the mask,
    so this is exactly C fused copies of ``attn_sublayer_decode`` (the
    prefill-vs-decode parity pin).  Global attention, no softcap (the
    serving path); rows past their prompt write out-of-range and are
    dropped (dense) or land on the scratch page (paged)."""
    if cfg.attn_logit_softcap:
        raise ValueError("chunked prefill does not support logit softcap")
    C = x.shape[1]
    h = layers.rms_norm(p["attn_norm"], x, cfg.rms_norm_eps)
    positions = _rope_positions(pos, C) if cfg.pos_embed == "rope" else None
    q, k, v = attn.qkv_project(
        p["attn"], h, positions=positions, rope_theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps,
    )
    q = scale_queries(q, cfg)
    if block_tables is not None:
        kc, vc = attn.update_paged_kv_cache(
            cache["k"], cache["v"], k, v, block_tables, pos
        )
        from repro.kernels.flash_decode.ref import gather_pages

        out = attn.chunk_decode_attention(
            q, gather_pages(kc, block_tables), gather_pages(vc, block_tables),
            pos,
        )
    else:
        kc, vc = attn.update_kv_cache_chunk(cache["k"], cache["v"], k, v, pos)
        out = attn.chunk_decode_attention(q, kc, vc, pos)
    return residual(x, attn.output_project(p["attn"], out), cfg), {
        "k": kc, "v": vc}


def init_ffn_layer(b: ParamBuilder, cfg: ArchConfig, kind: str) -> None:
    layers.init_rms_norm(b, "ffn_norm", cfg.d_model)
    if kind == "moe":
        moe_lib.init_moe(b, "moe", moe_dims(cfg))
    else:
        d_ff = cfg.d_ff_dense or cfg.d_ff
        layers.init_mlp(b, "mlp", cfg.d_model, d_ff)


def moe_dims(cfg: ArchConfig) -> moe_lib.MoEDims:
    return moe_lib.MoEDims(
        cfg.d_model,
        cfg.d_ff,
        cfg.num_experts,
        cfg.num_experts_per_tok,
        cfg.num_shared_experts,
        cfg.moe_capacity_factor,
    )


def ffn_sublayer(
    p: Params,
    x: jax.Array,
    cfg: ArchConfig,
    moe_impl: str = "sort",
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    h = layers.rms_norm(p["ffn_norm"], x, cfg.rms_norm_eps)
    if "moe" in p:
        out, aux = moe_lib.moe_ffn(
            p["moe"], h, moe_dims(cfg), impl=moe_impl, mesh=mesh
        )
        return residual(x, out, cfg), aux
    return residual(x, layers.mlp(p["mlp"], h), cfg), jnp.float32(0.0)


def init_cross_layer(b: ParamBuilder, cfg: ArchConfig) -> None:
    dims = attn.AttnDims(cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    layers.init_rms_norm(b, "cross_norm", cfg.d_model)
    attn.init_attention(b, "cross", dims, qk_norm=cfg.qk_norm)
    layers.init_rms_norm(b, "cross_ffn_norm", cfg.d_model)
    layers.init_mlp(b, "cross_mlp", cfg.d_model, cfg.d_ff)


def cross_sublayer(p: Params, x: jax.Array, mem_k, mem_v, cfg: ArchConfig):
    h = layers.rms_norm(p["cross_norm"], x, cfg.rms_norm_eps)
    x = x + attn.cross_attention(p["cross"], h, mem_k, mem_v)
    h = layers.rms_norm(p["cross_ffn_norm"], x, cfg.rms_norm_eps)
    return x + layers.mlp(p["cross_mlp"], h)


# ---------------------------------------------------------------------------
# Layer-pattern utilities
# ---------------------------------------------------------------------------


def layer_kinds(cfg: ArchConfig) -> list[str]:
    """Expand cfg.layer_pattern cyclically over num_layers."""
    pat = cfg.layer_pattern or "G"
    return [pat[i % len(pat)] for i in range(cfg.num_layers)]


def local_params(cfg: ArchConfig, kind: str) -> tuple[int, float]:
    """(window, rope_theta) for an attention layer of the given kind."""
    if kind == "L" or kind == "A":
        # local layers use the short rope theta (gemma3: 10k local / 1M global)
        return cfg.sliding_window, 10_000.0 if kind == "L" else cfg.rope_theta
    return 0, cfg.rope_theta


def is_uniform(cfg: ArchConfig) -> bool:
    kinds = set(layer_kinds(cfg))
    return len(kinds) == 1 and cfg.cross_attn_every == 0
