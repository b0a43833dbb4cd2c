"""Jitted V-trace wrapper with Pallas/TPU dispatch.

V-trace is the RL hot loop that every Sebulba learner step runs over the
full (B, T) trajectory batch.  On TPU it runs as a Pallas kernel
(time-major (T, block_b) columns in VMEM, the T-recursion sequential over
rows); elsewhere the jnp reference runs (identical math).  ``interpret=True`` exercises the
Pallas kernel on CPU for tests.

No gradients flow through v-trace targets (IMPALA treats vs / advantages as
constants), so the op needs no custom VJP.  The gradients stop at the
op's *inputs*: stopping them only at its outputs would still make
``jax.grad`` linearize the ``pallas_call`` (its inputs ``log_rhos`` and
``values`` carry tangents in every learner loss), which fails.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.vtrace.ref import VTraceOutput, vtrace_ref


@functools.partial(
    jax.jit, static_argnames=("clip_rho", "clip_c", "lambda_", "impl", "interpret")
)
def vtrace(
    log_rhos: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    *,
    clip_rho: float = 1.0,
    clip_c: float = 1.0,
    lambda_: float = 1.0,
    impl: str = "auto",
    interpret: bool = False,
) -> VTraceOutput:
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    log_rhos, discounts, rewards, values, bootstrap_value = (
        jax.lax.stop_gradient(x)
        for x in (log_rhos, discounts, rewards, values, bootstrap_value)
    )
    if impl == "pallas" or interpret:
        from repro.kernels.vtrace.vtrace import vtrace_pallas

        return vtrace_pallas(
            log_rhos, discounts, rewards, values, bootstrap_value,
            clip_rho=clip_rho, clip_c=clip_c, lambda_=lambda_,
            interpret=interpret,
        )
    return vtrace_ref(
        log_rhos, discounts, rewards, values, bootstrap_value,
        clip_rho=clip_rho, clip_c=clip_c, lambda_=lambda_,
    )
