"""Flash-decoding for TPU in Pallas: single-token attention over a long KV
cache (the Sebulba-actor / serve_step hot loop).

Grid: (B, num_s_blocks) — the cache-sequence dimension is the sequential
TPU grid axis; the online-softmax state for the K x G query heads lives in
VMEM scratch and persists across cache blocks.  Each grid step streams one
(block_s, K, h) tile of K and V — all kv heads, so the block's last two
dims equal the cache's (K, h) as Mosaic requires — and runs each kv head's
(block_s, h) slice through the MXU against its (G, h) query group, so the
kernel is purely HBM-bandwidth-bound — the roofline floor for decode.
Blocks whose positions are entirely masked (beyond ``pos`` or outside the
sliding window) are skipped with pl.when, so decode cost tracks the
*filled* cache length, not the allocated one.

Decode positions are **per row**: the scalar-prefetch ``pos`` vector holds
one int32 position per batch row (a scalar broadcasts), so rows of one
batch may sit at ragged depths — the continuous-batching serving invariant
(PR 10).  ``flash_decode_pallas_paged`` is the block-table variant: the KV
cache is a pool of fixed-size physical pages ``(P, bs, K, h)`` and a
prefetched ``(B, nb)`` block table maps row-local logical block ``si`` to
its physical page *in the BlockSpec index_map*, so the gather costs zero
extra copies — each grid step DMAs exactly the page the table names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    pos_ref,  # scalar prefetch: (B,) int32 per-row decode positions
    q_ref, k_ref, v_ref,  # inputs
    o_ref,  # output
    m_ref, l_ref, acc_ref,  # VMEM scratch
    *,
    block_s: int,
    num_s_blocks: int,
    window: int,
    sm_scale: float,
):
    si = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s_start = si * block_s
    run = s_start <= pos
    if window:
        run &= s_start + block_s - 1 > pos - window

    @pl.when(run)
    def _compute():
        k_pos = s_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], block_s), 1
        )
        valid = k_pos <= pos
        if window:
            valid &= k_pos > pos - window
        for kv in range(k_ref.shape[2]):  # static loop over the kv heads
            q = q_ref[0, kv].astype(jnp.float32) * sm_scale  # (G, h)
            k = k_ref[0, :, kv].astype(jnp.float32)  # (bs, h)
            v = v_ref[0, :, kv].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (G, bs)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[kv]  # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            scale = jnp.exp(m_prev - m_new)
            l_ref[kv] = l_ref[kv] * scale + p.sum(axis=1, keepdims=True)
            acc_ref[kv] = acc_ref[kv] * scale + jnp.dot(
                p, v, preferred_element_type=jnp.float32
            )
            m_ref[kv] = m_new

    @pl.when(si == num_s_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_decode_kernel(pos_ref, bt_ref, *rest, **kw):
    # the block table is consumed by the BlockSpec index_maps only; the
    # kernel body masks on logical positions exactly like the dense one
    del bt_ref
    _decode_kernel(pos_ref, *rest, **kw)


def _scratch(K: int, G: int, h: int) -> list:
    """Online-softmax state per kv head: running max, denominator, and
    output accumulator.  The max and denominator are (G, 1) columns: TPU
    scratch is at least 2-D."""
    return [
        pltpu.VMEM((K, G, 1), jnp.float32),
        pltpu.VMEM((K, G, 1), jnp.float32),
        pltpu.VMEM((K, G, h), jnp.float32),
    ]


def _pos_vector(pos, batch: int) -> jax.Array:
    """Scalar or (B,) position -> the (B,) int32 prefetch vector."""
    pos = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(pos.reshape(-1), (batch,)) if pos.ndim else (
        jnp.full((batch,), pos, jnp.int32)
    )


@functools.partial(
    jax.jit, static_argnames=("window", "block_s", "interpret")
)
def flash_decode_pallas(
    q: jax.Array,  # (B, 1, H, h)
    k_cache: jax.Array,  # (B, S, K, h)
    v_cache: jax.Array,  # (B, S, K, h)
    pos: jax.Array,  # scalar int32, or (B,) per-row positions
    *,
    window: int = 0,
    block_s: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, _, H, h = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"S={S} must divide block_s={block_s}")
    ns = S // block_s

    qh = q.reshape(B, K, G, h)  # (B, K, G, h)
    q_spec = pl.BlockSpec((1, K, G, h), lambda b, si, pos: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, block_s, K, h), lambda b, si, pos: (b, si, 0, 0)
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            block_s=block_s, num_s_blocks=ns, window=window,
            sm_scale=h**-0.5,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, ns),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=_scratch(K, G, h),
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, h), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(_pos_vector(pos, B), qh, k_cache, v_cache)
    return out.reshape(B, 1, H, h)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_pallas_paged(
    q: jax.Array,  # (B, 1, H, h)
    k_pages: jax.Array,  # (P, bs, K, h) physical page pool
    v_pages: jax.Array,  # (P, bs, K, h)
    block_tables: jax.Array,  # (B, nb) int32: logical block -> physical page
    pos: jax.Array,  # scalar int32, or (B,) per-row positions
    *,
    interpret: bool = False,
) -> jax.Array:
    """Paged flash-decode: the block table rides the scalar prefetch and
    the K/V BlockSpec index_maps dereference it, so the "gather" is just
    which page each sequential grid step DMAs.  Logical position
    ``s = si * bs + off`` masks exactly like the dense kernel; pages the
    table maps beyond ``pos`` are skipped (their content — stale data
    from a freed request, or the reserved scratch page — never loads).
    Global attention only (the serving path); window layers stay dense.
    """
    B, _, H, h = q.shape
    P, bs, K, _ = k_pages.shape
    nb = block_tables.shape[1]
    G = H // K

    qh = q.reshape(B, K, G, h)
    q_spec = pl.BlockSpec((1, K, G, h), lambda b, si, pos, bt: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, bs, K, h), lambda b, si, pos, bt: (bt[b, si], 0, 0, 0)
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            block_s=bs, num_s_blocks=nb, window=0, sm_scale=h**-0.5,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # pos, block_tables
            grid=(B, nb),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=_scratch(K, G, h),
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, h), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
    )(
        _pos_vector(pos, B),
        jnp.asarray(block_tables, jnp.int32),
        qh, k_pages, v_pages,
    )
    return out.reshape(B, 1, H, h)
