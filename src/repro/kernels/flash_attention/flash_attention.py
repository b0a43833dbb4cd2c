"""Flash attention for TPU in Pallas (prefill / training path).

Grid: (B, H, num_q_blocks, num_kv_blocks) — the last (kv) dimension is
sequential on TPU, so the online-softmax running state (m, l, acc) lives in
VMEM scratch and persists across kv steps.  BlockSpec index maps implement
GQA by pointing q-head ``head`` at kv-head ``head // G`` without
materializing broadcast K/V.  Causal q-blocks skip kv-blocks entirely in
the future (pl.when), so the causal kernel does ~half the work.

Block sizes default to (128, 128): MXU-aligned, and the VMEM working set
(q + k + v blocks + f32 accumulators) stays « 16 MB for head_dim ≤ 256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref,  # output
    m_ref, l_ref, acc_ref,  # scratch (f32)
    *,
    causal: bool,
    window: int,
    sm_scale: float,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = kj * block_kv

    # skip kv blocks strictly in the future of this q block (causal) or
    # entirely outside the sliding window
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window:
        run &= k_start + block_kv - 1 > q_start - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * sm_scale  # (bq, h)
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # (bkv, h)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = q @ k.T  # (bq, bkv)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        scale = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * scale + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * scale[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, T, H, h)
    k: jax.Array,  # (B, S, K, h)
    v: jax.Array,  # (B, S, K, h)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, T, H, h = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    block_q = min(block_q, T)
    block_kv = min(block_kv, S)
    if T % block_q or S % block_kv:
        raise ValueError(f"T={T}, S={S} must divide blocks ({block_q},{block_kv})")
    nq, nkv = T // block_q, S // block_kv

    # layout: heads-major so each grid step reads one (block, head_dim) tile
    qh = q.transpose(0, 2, 1, 3)  # (B, H, T, h)
    kh = k.transpose(0, 2, 1, 3)  # (B, K, S, h)
    vh = v.transpose(0, 2, 1, 3)

    grid = (B, H, nq, nkv)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            causal=causal, window=window, sm_scale=h**-0.5,
            block_q=block_q, block_kv=block_kv, num_kv_blocks=nkv,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, h), lambda b, hh, qi, kj: (b, hh, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, h), lambda b, hh, qi, kj: (b, hh // G, kj, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, h), lambda b, hh, qi, kj: (b, hh // G, kj, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, h), lambda b, hh, qi, kj: (b, hh, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, T, h), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),  # m: running max
            pltpu.VMEM((block_q,), jnp.float32),  # l: running sum
            pltpu.VMEM((block_q, h), jnp.float32),  # acc: weighted values
        ],
        interpret=interpret,
        name="flash_attention",
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)  # back to (B, T, H, h)
