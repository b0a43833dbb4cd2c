"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracle,
swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru_scan.ops import _assoc_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.rglru_scan.rglru_scan import rglru_scan_pallas
from repro.kernels.ssd_scan.ops import _chunked_ssd
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas
from repro.kernels.vtrace.ref import vtrace_ref
from repro.kernels.vtrace.vtrace import vtrace_pallas


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------- flash attn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,S,H,K,h,causal,window",
    [
        (2, 128, 128, 4, 2, 64, True, 0),
        (1, 256, 256, 4, 4, 32, True, 0),
        (2, 128, 128, 4, 1, 64, False, 0),  # MQA, non-causal
        (1, 256, 256, 2, 2, 64, True, 64),  # sliding window
        (1, 128, 128, 8, 2, 128, True, 0),  # GQA 4:1, wide head
    ],
)
def test_flash_attention_matches_ref(B, T, S, H, K, h, causal, window, dtype):
    ks = jax.random.split(jax.random.key(T + H + h), 3)
    q = jax.random.normal(ks[0], (B, T, H, h), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, h), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, h), jnp.float32).astype(dtype)
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=64, block_kv=64, interpret=True,
    )
    ref = attention_ref(q, k, v, causal=causal, window=window)
    assert out.dtype == dtype
    assert jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max() < _tol(dtype)


# ------------------------------------------------------------------ ssd scan


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,H,P,N,Q",
    [(2, 128, 4, 32, 16, 32), (1, 256, 2, 64, 32, 64), (2, 64, 8, 16, 8, 16)],
)
def test_ssd_scan_matches_ref(B, T, H, P, N, Q, dtype):
    ks = jax.random.split(jax.random.key(T + P), 5)
    x = jax.random.normal(ks[0], (B, T, H, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = (jax.random.normal(ks[3], (B, T, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, T, N)) * 0.3).astype(dtype)
    y_ref, s_ref = ssd_scan_ref(x, dt, A, Bm, Cm)
    y_chk, s_chk = _chunked_ssd(x, dt, A, Bm, Cm, Q, None)
    y_pal, s_pal = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=Q, interpret=True)
    tol = 0.05 if dtype == jnp.bfloat16 else 1e-4
    for y in (y_chk, y_pal):
        assert jnp.abs(y.astype(jnp.float32) - y_ref.astype(jnp.float32)).max() < tol
    for s in (s_chk, s_pal):
        assert jnp.abs(s - s_ref).max() < tol


# ---------------------------------------------------------------- rglru scan


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,W,bt,bw", [(2, 64, 128, 32, 64), (1, 128, 256, 64, 128)]
)
def test_rglru_matches_ref(B, T, W, bt, bw, dtype):
    ks = jax.random.split(jax.random.key(T + W), 3)
    x = jax.random.normal(ks[0], (B, T, W), jnp.float32).astype(dtype)
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (B, T, W))).astype(dtype)
    gi = jax.nn.sigmoid(jax.random.normal(ks[2], (B, T, W))).astype(dtype)
    y_ref, h_ref = rglru_scan_ref(x, a, gi)
    y_a, _ = _assoc_scan(x, a, gi, None)
    y_p, h_p = rglru_scan_pallas(x, a, gi, block_t=bt, block_w=bw, interpret=True)
    tol = _tol(dtype)
    assert jnp.abs(y_a.astype(jnp.float32) - y_ref.astype(jnp.float32)).max() < tol
    assert jnp.abs(y_p.astype(jnp.float32) - y_ref.astype(jnp.float32)).max() < tol
    assert jnp.abs(h_p - h_ref).max() < tol


def test_rglru_h0_custom_vjp_matches_scan_autodiff():
    """The linear-memory custom VJP on the h0 != None path (the R2D2
    stored-state unroll) must produce the same gradients — including dh0
    and the h_T output cotangent — as plain autodiff through the
    sequential lax.scan reference."""
    ks = jax.random.split(jax.random.key(7), 4)
    B, T, W = 2, 24, 8
    x = jax.random.normal(ks[0], (B, T, W))
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (B, T, W)))
    gi = jax.nn.sigmoid(jax.random.normal(ks[2], (B, T, W)))
    h0 = jax.random.normal(ks[3], (B, W))
    cy = jax.random.normal(jax.random.key(9), (B, T, W))
    ch = jax.random.normal(jax.random.key(10), (B, W))

    def loss(fn):
        def inner(x, a, gi, h0):
            y, hT = fn(x, a, gi, h0)
            return jnp.sum(y * cy) + jnp.sum(hT * ch)

        return jax.grad(inner, argnums=(0, 1, 2, 3))

    g_ops = loss(lambda *args: _assoc_scan(*args))(x, a, gi, h0)
    g_ref = loss(rglru_scan_ref)(x, a, gi, h0)
    for go, gr in zip(g_ops, g_ref):
        assert jnp.abs(go - gr).max() < 1e-4


def test_rglru_carry_state():
    """Scan from h0 equals splitting the sequence in two (ops path)."""
    ks = jax.random.split(jax.random.key(0), 3)
    B, T, W = 2, 32, 16
    x = jax.random.normal(ks[0], (B, T, W))
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (B, T, W)))
    gi = jax.nn.sigmoid(jax.random.normal(ks[2], (B, T, W)))
    y_full, h_full = rglru_scan_ref(x, a, gi)
    y1, h1 = rglru_scan_ref(x[:, :16], a[:, :16], gi[:, :16])
    y2, h2 = rglru_scan_ref(x[:, 16:], a[:, 16:], gi[:, 16:], h0=h1)
    assert jnp.abs(jnp.concatenate([y1, y2], 1) - y_full).max() < 1e-5
    assert jnp.abs(h2 - h_full).max() < 1e-5


# -------------------------------------------------------------------- vtrace


@pytest.mark.parametrize(
    "B,T,bb",
    [
        (8, 32, 8), (16, 100, 4), (4, 7, 4),
        # B not a multiple of block_b: the kernel pads rows up to the block
        # (it used to raise here, with an inverted error message)
        (10, 12, 4), (5, 9, 4), (3, 6, 2),
    ],
)
def test_vtrace_matches_ref(B, T, bb):
    ks = jax.random.split(jax.random.key(B * T), 5)
    lr = jax.random.normal(ks[0], (B, T)) * 0.3
    disc = (jax.random.uniform(ks[1], (B, T)) > 0.1).astype(jnp.float32) * 0.99
    rew = jax.random.normal(ks[2], (B, T))
    val = jax.random.normal(ks[3], (B, T))
    boot = jax.random.normal(ks[4], (B,))
    o_ref = vtrace_ref(lr, disc, rew, val, boot)
    o_p = vtrace_pallas(lr, disc, rew, val, boot, block_b=bb, interpret=True)
    assert jnp.abs(o_p.vs - o_ref.vs).max() < 1e-5
    assert jnp.abs(o_p.pg_advantages - o_ref.pg_advantages).max() < 1e-5


def test_impala_loss_grad_through_pallas_vtrace_matches_jnp(monkeypatch):
    """``jax.grad`` of the IMPALA loss with the Pallas V-trace (interpret
    mode) gives the jnp path's values and gradients: the kernel's inputs
    stop gradients, so autodiff never linearizes the ``pallas_call``."""
    import functools

    from repro.kernels.vtrace import ops
    from repro.rl import losses

    B, T, A = 6, 9, 4
    ks = jax.random.split(jax.random.key(5), 6)
    logits = jax.random.normal(ks[0], (B, T, A))
    values = jax.random.normal(ks[1], (B, T))
    actions = jax.random.randint(ks[2], (B, T), 0, A)
    blogp = jnp.log(jax.random.uniform(ks[3], (B, T), minval=0.2, maxval=0.9))
    rewards = jax.random.normal(ks[4], (B, T))
    discounts = jnp.full((B, T), 0.99)
    boot = jax.random.normal(ks[5], (B,))

    def value_and_grad():
        def total(logits, values):
            return losses.weighted_impala_loss(
                logits, values, actions, blogp, rewards, discounts, boot
            ).total

        return jax.value_and_grad(total, argnums=(0, 1))(logits, values)

    monkeypatch.setattr(
        losses, "vtrace", functools.partial(ops.vtrace, impl="jnp")
    )
    want = value_and_grad()
    monkeypatch.setattr(
        losses, "vtrace", functools.partial(ops.vtrace, interpret=True)
    )
    got = value_and_grad()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.abs(g - w).max() < 1e-5
