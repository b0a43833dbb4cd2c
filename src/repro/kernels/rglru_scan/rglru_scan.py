"""RG-LRU linear recurrence as a Pallas TPU kernel.

Grid: (B, num_width_blocks, num_time_blocks) — time is the sequential TPU
grid dimension; the hidden state (one row of width ``block_w``) is carried
in VMEM scratch across time blocks.  Within a time block the recurrence
runs as a lax.fori_loop over the rows of f32 VMEM scratch (Mosaic
addresses single rows of 32-bit data only) — the same structure as the
custom linear-scan kernel the Griffin paper used on TPU (sequential in
time, fully parallel in batch x width on the VPU lanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, a_ref, i_ref, y_ref, h_ref, a32_ref, y32_ref):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # stage the block in f32 scratch: u = beta * (i * x) in the output
    # buffer, the decay gate beside it
    a = a_ref[0].astype(jnp.float32)  # (bt, bw)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0))
    a32_ref[...] = a
    y32_ref[...] = beta * (i_ref[0].astype(jnp.float32) * x_ref[0].astype(
        jnp.float32
    ))

    def step(t, h):
        # one (1, bw) f32 row per step, read and written through the refs:
        # the time index is a sublane offset, never a slice of a value
        row = pl.ds(t, 1)
        h = a32_ref[row, :] * h + y32_ref[row, :]
        y32_ref[row, :] = h
        return h

    h_ref[...] = jax.lax.fori_loop(0, a.shape[0], step, h_ref[...])
    y_ref[0] = y32_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_w", "interpret"))
def rglru_scan_pallas(
    x: jax.Array,  # (B, T, W)
    a: jax.Array,  # (B, T, W) decay gates in (0, 1)
    gate_i: jax.Array,  # (B, T, W) input gates
    h0=None,  # kernel path starts from zero state
    *,
    block_t: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    if h0 is not None:
        raise NotImplementedError("kernel path starts from zero state")
    B, T, W = x.shape
    bt = min(block_t, T)
    bw = min(block_w, W)
    if T % bt or W % bw:
        raise ValueError(f"(T={T}, W={W}) must divide blocks ({bt}, {bw})")
    grid = (B, W // bw, T // bt)

    spec = pl.BlockSpec((1, bt, bw), lambda b, wi, ti: (b, ti, wi))
    y = pl.pallas_call(
        _rglru_kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, T, W), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, bw), jnp.float32),  # h, carried across blocks
            pltpu.VMEM((bt, bw), jnp.float32),
            pltpu.VMEM((bt, bw), jnp.float32),
        ],
        interpret=interpret,
        name="rglru_scan",
    )(x, a, gate_i)
    return y, y[:, -1].astype(jnp.float32)
