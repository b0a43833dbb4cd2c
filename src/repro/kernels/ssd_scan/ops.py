"""Jitted SSD scan wrapper: chunked matmul formulation (TPU-native).

The chunked state-space-duality algorithm (Dao & Gu, arXiv:2405.21060 §6)
re-expresses the linear recurrence as per-chunk attention-like matmuls plus a
short sequential scan over chunk boundary states — this maps the SSM onto
the MXU instead of a length-T elementwise loop.

Dispatch: on TPU backends the Pallas kernel (ssd_scan.py) is used; elsewhere
(CPU dry-run, tests) the identical chunked algorithm runs as pure jnp.
``interpret=True`` forces the Pallas kernel in interpreter mode for kernel
tests on CPU.  The kernel has no backward of its own: its gradient is the
jnp chunk scan's memory-bounded VJP (``_ssd_pallas``), which re-runs one
chunk at a time, so a learner differentiates through the TPU forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _chunked_ssd(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    chunk: int,
    init_state: jax.Array | None,
) -> tuple[jax.Array, jax.Array]:
    Bsz, T, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, T)
    if T % Q:
        raise ValueError(f"seq len {T} not divisible by chunk {Q}")
    nc = T // Q

    xf = x.astype(jnp.float32).reshape(Bsz, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, Q, H)
    Bf = Bm.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Cf = Cm.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Af = A.astype(jnp.float32)

    # cumulative log-decay within each chunk, inclusive of step t
    logdec = dtf * Af  # (B, nc, Q, H)
    cum = jnp.cumsum(logdec, axis=2)  # L[t] = sum_{tau<=t} dt_tau * A

    # intra-chunk: M[t,s] = (C_t . B_s) * exp(L[t]-L[s]) for s<=t
    scores = jnp.einsum("bcqn,bcsn->bcqs", Cf, Bf)  # (B,nc,Q,Q)
    delta = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q(t),Q(s),H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # masked before the exp: exp of the (positive) s > t entries overflows,
    # and inf * 0 in the backward pass would be NaN
    decay_m = jnp.exp(jnp.where(causal[None, None, :, :, None], delta, -jnp.inf))
    attn = scores[..., None] * decay_m  # (B,nc,Q,Q,H)
    dx = xf * dtf[..., None]  # (B,nc,Q,H,P)
    y_intra = jnp.einsum("bcqsh,bcshp->bcqhp", attn, dx)

    # chunk-final states: S_c = sum_s exp(L[Q-1]-L[s]) dx_s (x) B_s
    tail = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,Q,H)
    S_c = jnp.einsum("bcqh,bcqhp,bcqn->bchpn", tail, dx, Bf)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H) total decay per chunk

    # sequential inter-chunk scan (nc steps)
    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def step(S_prev, inp):
        S_chunk, dec = inp  # (B,H,P,N), (B,H)
        S_new = S_prev * dec[..., None, None] + S_chunk
        return S_new, S_prev

    (S_final, S_prevs) = jax.lax.scan(
        step,
        init_state.astype(jnp.float32),
        (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    S_prevs = jnp.moveaxis(S_prevs, 0, 1)  # (B,nc,H,P,N) state entering chunk

    # inter-chunk contribution: y_t += C_t . (exp(L[t]) * S_prev)
    y_inter = jnp.einsum(
        "bcqn,bcqh,bchpn->bcqhp", Cf, jnp.exp(cum), S_prevs
    )
    y = (y_intra + y_inter).reshape(Bsz, T, H, P).astype(x.dtype)
    return y, S_final


def _ssd_one_chunk(S_prev, xc, dtc, Bc, Cc, A):
    """One chunk of the SSD duality.  S_prev: (B,H,P,N); xc: (B,Q,H,P);
    dtc: (B,Q,H); Bc/Cc: (B,Q,N); A: (H,).  Returns (y_c, S_new)."""
    logdec = dtc * A  # (B,Q,H)
    cum = jnp.cumsum(logdec, axis=1)
    Q = xc.shape[1]
    scores = jnp.einsum("bqn,bsn->bqs", Cc, Bc)
    delta = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Q,S,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # masked before the exp (see ``_chunked_ssd``): the VJP stays finite
    decay = jnp.exp(jnp.where(causal[None, :, :, None], delta, -jnp.inf))
    attn = scores[..., None] * decay  # (B,Q,S,H)
    dx = xc * dtc[..., None]
    y_intra = jnp.einsum("bqsh,bshp->bqhp", attn, dx)
    y_inter = jnp.einsum("bqn,bqh,bhpn->bqhp", Cc, jnp.exp(cum), S_prev)
    tail = jnp.exp(cum[:, -1:, :] - cum)
    S_new = S_prev * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
        "bqh,bqhp,bqn->bhpn", tail, dx, Bc
    )
    return y_intra + y_inter, S_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_chunk_scan(x, dt, A, Bm, Cm, nc):
    y, S_final, _ = _ssd_chunk_scan_fwd_impl(x, dt, A, Bm, Cm, nc)
    return y, S_final


def _ssd_chunk_scan_fwd_impl(x, dt, A, Bm, Cm, nc):
    Bsz, T, H, P = x.shape
    N = Bm.shape[-1]
    Q = T // nc
    xc = x.astype(jnp.float32).reshape(Bsz, nc, Q, H, P)
    dtc = dt.astype(jnp.float32).reshape(Bsz, nc, Q, H)
    Bc = Bm.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Cc = Cm.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Af = A.astype(jnp.float32)

    def step(S, inp):
        xi, di, bi, ci = inp
        y, S_new = _ssd_one_chunk(S, xi, di, bi, ci, Af)
        return S_new, (y, S)  # also emit the INCOMING state (bwd residual)

    S0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), (xc, dtc, Bc, Cc))
    S_final, (ys, S_prevs) = jax.lax.scan(step, S0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, T, H, P).astype(x.dtype)
    return y, S_final, S_prevs  # S_prevs: (nc, B, H, P, N)


def _ssd_vjp_fwd(x, dt, A, Bm, Cm, nc):
    y, S_final, S_prevs = _ssd_chunk_scan_fwd_impl(x, dt, A, Bm, Cm, nc)
    return (y, S_final), (x, dt, A, Bm, Cm, S_prevs)


def _ssd_vjp_bwd(nc_static, res, cts):
    """Reverse scan over chunks: each step re-runs ONE chunk under jax.vjp —
    live memory is a single chunk's intermediates plus the (nc, B, H, P, N)
    state checkpoints, instead of every chunk's (B, Q, Q, H) decay/attn
    tensors (the dominant mamba2 training-memory term)."""
    dy, dS_final = cts
    x, dt, A, Bm, Cm, S_prevs = res
    Bsz, T, H, P = x.shape
    N = Bm.shape[-1]
    nc = nc_static
    Q = T // nc
    xc = x.astype(jnp.float32).reshape(Bsz, nc, Q, H, P)
    dtc = dt.astype(jnp.float32).reshape(Bsz, nc, Q, H)
    Bc = Bm.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Cc = Cm.astype(jnp.float32).reshape(Bsz, nc, Q, N)
    Af = A.astype(jnp.float32)
    dyc = dy.astype(jnp.float32).reshape(Bsz, nc, Q, H, P)

    def step(carry, inp):
        dS, dA_acc = carry  # cotangent wrt the chunk's OUTPUT state
        xi, di, bi, ci, dyi, S_prev = inp

        def f(S, xi, di, bi, ci, A):
            return _ssd_one_chunk(S, xi, di, bi, ci, A)

        _, vjp = jax.vjp(f, S_prev, xi, di, bi, ci, Af)
        dS_prev, dxi, ddi, dbi, dci, dAi = vjp((dyi, dS))
        return (dS_prev, dA_acc + dAi), (dxi, ddi, dbi, dci)

    xs = jax.tree.map(
        lambda a: jnp.moveaxis(a, 1, 0) if a.ndim > 3 else a,
        (xc, dtc, Bc, Cc, dyc),
    )
    xs = xs + (S_prevs,)
    dS0 = dS_final.astype(jnp.float32)
    (dS_first, dA), (dxs, ddts, dBs, dCs) = jax.lax.scan(
        step, (dS0, jnp.zeros_like(Af)), xs, reverse=True
    )
    del dS_first
    dx = jnp.moveaxis(dxs, 0, 1).reshape(Bsz, T, H, P).astype(x.dtype)
    ddt = jnp.moveaxis(ddts, 0, 1).reshape(Bsz, T, H).astype(dt.dtype)
    dB = jnp.moveaxis(dBs, 0, 1).reshape(Bsz, T, N).astype(Bm.dtype)
    dC = jnp.moveaxis(dCs, 0, 1).reshape(Bsz, T, N).astype(Cm.dtype)
    return dx, ddt, dA.astype(A.dtype), dB, dC


_ssd_chunk_scan.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_pallas(x, dt, A, Bm, Cm, nc, interpret):
    from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas

    return ssd_scan_pallas(
        x, dt, A, Bm, Cm, chunk=x.shape[1] // nc, interpret=interpret
    )


def _ssd_pallas_fwd(x, dt, A, Bm, Cm, nc, interpret):
    return _ssd_pallas(x, dt, A, Bm, Cm, nc, interpret), (x, dt, A, Bm, Cm)


def _ssd_pallas_bwd(nc, interpret, res, cts):
    """The Pallas forward's gradient is the jnp chunk-scan VJP
    (``_ssd_vjp_bwd``), fed the state entering each chunk: zero for the
    first chunk, and for later ones recomputed here by the jnp chunk scan,
    so that the kernel writes no per-chunk states it does not need."""
    x, dt, A, Bm, Cm = res
    Bsz, _, H, P = x.shape
    if nc == 1:
        S_prevs = jnp.zeros((1, Bsz, H, P, Bm.shape[-1]), jnp.float32)
    else:
        S_prevs = _ssd_chunk_scan_fwd_impl(x, dt, A, Bm, Cm, nc)[2]
    return _ssd_vjp_bwd(nc, (x, dt, A, Bm, Cm, S_prevs), cts)


_ssd_pallas.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)


def _pad_time(a: jax.Array, T_pad: int) -> jax.Array:
    pad = [(0, 0)] * a.ndim
    pad[1] = (0, T_pad - a.shape[1])
    return jnp.pad(a, pad)


@functools.partial(jax.jit, static_argnames=("chunk", "impl", "interpret"))
def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    init_state: jax.Array | None = None,
    *,
    chunk: int = 256,
    impl: str = "auto",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  See ref.py for shapes.

    Any length T: a sequence that is not a whole number of chunks is
    padded at its end with zero steps (dt = 0: no decay, no input), which
    leaves every earlier output and the final state exactly as they were,
    since the scan is causal; the padded outputs are dropped.  A sequence
    shorter than a chunk is one chunk of its own length rounded up to 8
    rows.  The Pallas forward (the TPU's) differentiates through the jnp
    chunk scan's VJP.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    T = x.shape[1]
    Q = min(chunk, -(-T // 8) * 8)
    nc = -(-T // Q)
    if nc * Q != T:
        x, dt, Bm, Cm = (_pad_time(a, nc * Q) for a in (x, dt, Bm, Cm))
    if impl == "pallas" or interpret:
        if init_state is not None:
            raise NotImplementedError("kernel path starts from zero state")
        y, S = _ssd_pallas(x, dt, A, Bm, Cm, nc, interpret)
    elif impl == "jnp" and init_state is None:
        # chunk-scan layout with the memory-bounded custom VJP (§Perf):
        # backward re-runs one chunk at a time instead of saving every
        # chunk's (B, Q, Q, H) attn/decay tensors.
        y, S = _ssd_chunk_scan(x, dt, A, Bm, Cm, nc)
    else:
        y, S = _chunked_ssd(x, dt, A, Bm, Cm, Q, init_state)
    return y[:, :T], S
