"""Mamba-2 (SSD) block [arXiv:2405.21060].

Block: RMSNorm -> fused in_proj to (z, x, B, C, dt) -> causal depthwise conv
over (x, B, C) -> SSD scan -> D skip -> gated RMSNorm -> out_proj.

Decode keeps two pieces of state per layer:
  * ssm  : (B, H, P, N) SSD state
  * conv : (B, conv_width-1, conv_dim) rolling window of recent conv inputs
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_step_ref
from repro.models import layers
from repro.param import ParamBuilder, constant_init, fan_in_init, normal_init, zeros_init


def conv_dim(cfg: ArchConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_state


def init_mamba2_block(b: ParamBuilder, name: str, cfg: ArchConfig) -> None:
    d, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    proj_out = 2 * di + 2 * N + H  # z, x, B, C, dt
    with b.scope(name):
        layers.init_rms_norm(b, "norm", d)
        b.param("in_proj", (d, proj_out), ("embed", "ssm_inner"), fan_in_init())
        b.param(
            "conv_w",
            (cfg.conv_width, conv_dim(cfg)),
            ("conv_width", "ssm_inner"),
            normal_init(0.1),
        )
        b.param("conv_b", (conv_dim(cfg),), ("ssm_inner",), zeros_init(),
                dtype=jnp.float32)
        b.param("A_log", (H,), ("ssm_heads",), constant_init(0.0), dtype=jnp.float32)
        b.param("dt_bias", (H,), ("ssm_heads",), constant_init(0.5), dtype=jnp.float32)
        b.param("D", (H,), ("ssm_heads",), constant_init(1.0), dtype=jnp.float32)
        layers.init_rms_norm(b, "out_norm", di)
        b.param("out_proj", (di, d), ("ssm_inner", "embed"), fan_in_init())


def _split_proj(cfg: ArchConfig, proj: jax.Array):
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = proj[..., :di]
    xc = proj[..., di : 2 * di]
    Bm = proj[..., 2 * di : 2 * di + N]
    Cm = proj[..., 2 * di + N : 2 * di + 2 * N]
    dt = proj[..., 2 * di + 2 * N : 2 * di + 2 * N + H]
    return z, xc, Bm, Cm, dt


def _causal_conv(params, u: jax.Array, width: int) -> jax.Array:
    """Depthwise causal conv along T.  u: (B, T, C)."""
    pad = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + u.shape[1]] * params["conv_w"][i].astype(u.dtype)
        for i in range(width)
    )
    return jax.nn.silu(
        (out + params["conv_b"].astype(jnp.float32).astype(u.dtype))
    )


def mamba2_block(params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Train/prefill forward.  x: (B, T, D) -> (B, T, D)."""
    Bsz, T, _ = x.shape
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    h = layers.rms_norm(params["norm"], x, cfg.rms_norm_eps)
    proj = h @ params["in_proj"].astype(h.dtype)
    z, xc, Bm, Cm, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xc, Bm, Cm], axis=-1)
    conv_out = _causal_conv(params, conv_in, cfg.conv_width)
    xc = conv_out[..., : cfg.d_inner]
    Bm = conv_out[..., cfg.d_inner : cfg.d_inner + cfg.ssm_state]
    Cm = conv_out[..., cfg.d_inner + cfg.ssm_state :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,T,H)
    A = -jnp.exp(params["A_log"])  # (H,) negative decay
    xh = xc.reshape(Bsz, T, H, P)
    y, _ = ssd_scan(xh, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    y = y + params["D"].astype(y.dtype)[:, None] * xh
    y = y.reshape(Bsz, T, cfg.d_inner)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    y = layers.rms_norm(params["out_norm"], y, cfg.rms_norm_eps)
    return y @ params["out_proj"].astype(y.dtype)


def init_mamba2_cache(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16) -> dict:
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return {
        "ssm": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim(cfg)), dtype),
    }


def mamba2_decode_step(
    params, cache: dict, x: jax.Array, cfg: ArchConfig
) -> tuple[jax.Array, dict]:
    """x: (B, 1, D) one token; returns (out (B,1,D), new cache)."""
    Bsz = x.shape[0]
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    h = layers.rms_norm(params["norm"], x, cfg.rms_norm_eps)[:, 0]  # (B, D)
    proj = h @ params["in_proj"].astype(h.dtype)
    z, xc, Bm, Cm, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xc, Bm, Cm], axis=-1)  # (B, C)
    window = jnp.concatenate([cache["conv"], conv_in[:, None]], axis=1)  # (B, W, C)
    w = params["conv_w"].astype(conv_in.dtype)  # (W, C)
    conv_out = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", window, w)
        + params["conv_b"].astype(conv_in.dtype)
    )
    xc = conv_out[..., : cfg.d_inner]
    Bm = conv_out[..., cfg.d_inner : cfg.d_inner + cfg.ssm_state]
    Cm = conv_out[..., cfg.d_inner + cfg.ssm_state :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B, H)
    A = -jnp.exp(params["A_log"])
    y, S = ssd_step_ref(cache["ssm"], xc.reshape(Bsz, H, P), dt, A, Bm, Cm)
    y = y + params["D"].astype(y.dtype)[:, None] * xc.reshape(Bsz, H, P)
    y = y.reshape(Bsz, cfg.d_inner)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    y = layers.rms_norm(params["out_norm"], y[:, None], cfg.rms_norm_eps)[:, 0]
    out = (y @ params["out_proj"].astype(y.dtype))[:, None]
    new_cache = {"ssm": S, "conv": window[:, 1:]}
    return out, new_cache
