"""V-trace reverse recursion as a Pallas TPU kernel.

Grid: (num_batch_blocks,).  The kernel works time-major: a block of
trajectory columns (T, block_b) is resident in VMEM with the batch on the
lanes, and the reverse time recursion runs as a fori_loop that reads and
writes one (1, block_b) row of the refs per step — the time index is a
sublane offset into a ref, never a lane slice of a value, which Mosaic
cannot lower.  One pass computes both vs and pg_advantages — fusing what
would otherwise be two XLA while-loops over T.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _vtrace_kernel(
    logr_ref, disc_ref, rew_ref, val_ref, boot_ref,  # (T, bb); boot (1, bb)
    vs_ref, adv_ref,
    *,
    clip_rho: float,
    clip_c: float,
    lambda_: float,
    T: int,
):
    def step(i, carry):
        # carry: (vs_{t+1} - V_{t+1}, V_{t+1}, vs_{t+1}), each (1, bb)
        acc, v_tp1, vs_tp1 = carry
        t = pl.ds(T - 1 - i, 1)
        rho = jnp.exp(logr_ref[t, :])
        clipped = jnp.minimum(clip_rho, rho)
        c = lambda_ * jnp.minimum(clip_c, rho)
        disc, rew, val = disc_ref[t, :], rew_ref[t, :], val_ref[t, :]
        delta = clipped * (rew + disc * v_tp1 - val)
        acc = delta + disc * c * acc
        vs = val + acc
        vs_ref[t, :] = vs
        adv_ref[t, :] = clipped * (rew + disc * vs_tp1 - val)
        return acc, val, vs

    boot = boot_ref[...]
    jax.lax.fori_loop(0, T, step, (jnp.zeros_like(boot), boot, boot))


@functools.partial(
    jax.jit,
    static_argnames=("clip_rho", "clip_c", "lambda_", "block_b", "interpret"),
)
def vtrace_pallas(
    log_rhos: jax.Array,  # (B, T)
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,  # (B,)
    *,
    clip_rho: float = 1.0,
    clip_c: float = 1.0,
    lambda_: float = 1.0,
    block_b: int = 128,
    interpret: bool = False,
):
    from repro.kernels.vtrace.ref import VTraceOutput

    B, T = log_rhos.shape
    bb = min(block_b, B)
    # pad B up to a multiple of the batch block instead of restricting the
    # caller to divisible shapes: padded rows cost one extra grid step at
    # most and compute benign values (log_rho 0 -> rho 1, everything else
    # 0), which are sliced off before returning
    B_pad = -(-B // bb) * bb
    # time-major f32 columns (T, B_pad); the batch rides the lanes
    cols = lambda x: jnp.pad(
        x.astype(jnp.float32).reshape(B, -1), ((0, B_pad - B), (0, 0))
    ).T
    spec = pl.BlockSpec((T, bb), lambda i: (0, i))
    spec_boot = pl.BlockSpec((1, bb), lambda i: (0, i))
    vs, adv = pl.pallas_call(
        functools.partial(
            _vtrace_kernel, clip_rho=clip_rho, clip_c=clip_c,
            lambda_=lambda_, T=T,
        ),
        grid=(B_pad // bb,),
        in_specs=[spec, spec, spec, spec, spec_boot],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((T, B_pad), jnp.float32)] * 2,
        interpret=interpret,
    )(
        cols(log_rhos), cols(discounts), cols(rewards), cols(values),
        cols(bootstrap_value),
    )
    return VTraceOutput(vs=vs.T[:B], pg_advantages=adv.T[:B])
