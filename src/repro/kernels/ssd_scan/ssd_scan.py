"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid: (B, H, num_chunks) — the chunk dimension is sequential on TPU, so the
inter-chunk SSM state (P, N) lives in VMEM scratch and persists across
chunks (exactly the carry of the chunked SSD algorithm).  Per grid step the
kernel does the three matmuls of the state-space-duality formulation
(intra-chunk "attention", inter-chunk state read-out, chunk-state update) —
all MXU work on (Q x Q), (Q x N) and (P x N) tiles.

This is the TPU-native adaptation: the original CUDA kernel leans on warp
shuffles for the recurrence; on TPU we rephrase the whole chunk as matmuls
(as §6 of the paper itself suggests) and let the sequential grid carry the
state in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref,  # inputs
    y_ref, s_out_ref,  # outputs
    state_ref,  # scratch: (P, N) f32 carried across chunks
    *,
    num_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (1, Q) row
    A = a_ref[pl.program_id(1)]  # this head's scalar decay, from SMEM
    Bm = b_ref[0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)  # (Q, N)
    Q = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = row >= col

    # the kernel needs dt and the inclusive log-decay cumsum both as rows
    # and as columns; masked lane and sublane sums build each form from the
    # one (1, Q) row, with no transpose or cumsum inside the kernel
    dt_col = jnp.sum(jnp.where(row == col, dt, 0.0), axis=1, keepdims=True)
    logdec = dt * A  # (1, Q)
    cum_col = jnp.sum(jnp.where(causal, logdec, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(
        jnp.where(row <= col, dt_col * A, 0.0), axis=0, keepdims=True
    )  # (1, Q)

    # intra-chunk: M[t,s] = (C_t . B_s) exp(cum_t - cum_s), s <= t
    scores = _dot_nt(Cm, Bm)  # (Q, Q)
    attn = jnp.where(causal, scores * jnp.exp(cum_col - cum_row), 0.0)
    dx = x * dt_col  # (Q, P)
    y_intra = jnp.dot(attn, dx, preferred_element_type=jnp.float32)

    # inter-chunk: y_t += exp(cum_t) * C_t . S_prev
    state = state_ref[...]  # (P, N)
    y_inter = jnp.exp(cum_col) * _dot_nt(Cm, state)  # (Q, P)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S = exp(cum_Q) S_prev + sum_s exp(cum_Q - cum_s) dx_s (x) B_s
    last = cum_row[:, Q - 1 :]  # (1, 1)
    tail = jnp.exp(last - cum_col)  # (Q, 1)
    state_new = state * jnp.exp(last) + jax.lax.dot_general(
        dx * tail, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    state_ref[...] = state_new

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        s_out_ref[0, 0] = state_new


def _dot_nt(a, b):
    """a @ b.T with the transpose folded into the contraction."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jax.Array,  # (B, T, H, P)
    dt: jax.Array,  # (B, T, H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, T, N)
    Cm: jax.Array,  # (B, T, N)
    init_state=None,  # unsupported in the kernel path (always zero)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    if init_state is not None:
        raise NotImplementedError("kernel path starts from zero state")
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, T)
    if T % Q:
        raise ValueError(f"T={T} must divide chunk={Q}")
    nc = T // Q

    # head-major layouts; dt is a (1, T) row per head, so its blocked
    # dims (1, Q) are the last two, as Mosaic requires
    xh = x.transpose(0, 2, 1, 3)  # (B, H, T, P)
    dth = dt.transpose(0, 2, 1)[:, :, None, :]  # (B, H, 1, T)

    grid = (B, H, nc)
    y, s_final = pl.pallas_call(
        functools.partial(_ssd_kernel, num_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, hh, c: (b, hh, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # A: (H,) scalars
            pl.BlockSpec((1, Q, N), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, hh, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xh, dth, A.astype(jnp.float32), Bm, Cm)
    return y.transpose(0, 2, 1, 3), s_final
