"""Plain float32 references for the training cells, and the readings that
compare the program with them.

Imports nothing of the program.  Every contraction goes through a
``Numerics`` object: ``HIGHEST`` is the reference (float32 operands,
``precision="highest"``); ``FP8`` is the control, the same reference with
the operands of every contraction rounded to float8 e4m3 under a
per-tensor scale, the step below the bfloat16 that the configurations
state.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
E4M3_MAX = 448.0


def _q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under a per-tensor scale (largest |x| maps to
    the format's largest value), and back to float32."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


class Numerics(NamedTuple):
    name: str
    quantize: bool

    def cast(self, x):
        x = jnp.asarray(x).astype(F32)
        return _q8(x) if self.quantize else x

    def einsum(self, eq, a, b):
        return jnp.einsum(
            eq, self.cast(a), self.cast(b),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32,
        )

    def conv(self, x, w):
        """Stride-1 "same" convolution, NHWC x HWIO, written as the
        product of the image's shifted patches with the kernel (a float32
        convolution at highest precision takes the TPU's compiler tens of
        minutes; the product compiles in seconds)."""
        kh, kw, cin, cout = w.shape
        N, H, W, _ = x.shape
        xp = jnp.pad(x, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
        cols = jnp.concatenate(
            [xp[:, i:i + H, j:j + W, :] for i in range(kh) for j in range(kw)],
            axis=-1)
        return self.einsum("nhwk,ko->nhwo", cols, w.reshape(kh * kw * cin, cout))


HIGHEST = Numerics("highest", False)
FP8 = Numerics("fp8", True)


# ----------------------------------------------------------------- losses


def log_softmax(logits):
    logits = logits.astype(F32)
    return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)


def vtrace(log_rhos, discounts, rewards, values, bootstrap, *, clip_rho=1.0,
           clip_c=1.0):
    """V-trace targets and policy-gradient advantages (Espeholt et al.
    2018, eq. 1), batch-major (B, T), by a plain reverse loop over T."""
    rhos = jnp.exp(log_rhos)
    crho = jnp.minimum(clip_rho, rhos)
    cs = jnp.minimum(clip_c, rhos)
    T = rewards.shape[1]
    v_tp1 = jnp.concatenate([values[:, 1:], bootstrap[:, None]], axis=1)
    deltas = crho * (rewards + discounts * v_tp1 - values)
    acc = jnp.zeros_like(bootstrap)
    errs = [None] * T
    for t in range(T - 1, -1, -1):
        acc = deltas[:, t] + discounts[:, t] * cs[:, t] * acc
        errs[t] = acc
    vs = values + jnp.stack(errs, axis=1)
    vs_tp1 = jnp.concatenate([vs[:, 1:], bootstrap[:, None]], axis=1)
    adv = crho * (rewards + discounts * vs_tp1 - values)
    return vs, adv


def impala_terms(logits, values, bootstrap, traj, *, entropy_cost,
                 value_cost, clip_rho=1.0, clip_c=1.0):
    """The V-trace actor-critic loss on (B, T) rows: policy gradient +
    value_cost * value - entropy_cost * entropy, each a mean over B x T.
    V-trace targets are constants (no gradient through them)."""
    logp_all = log_softmax(logits)
    logp = jnp.take_along_axis(
        logp_all, traj["actions"][..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    sg = jax.lax.stop_gradient
    vs, adv = vtrace(
        sg(logp - traj["behaviour_logp"]), traj["discounts"],
        traj["rewards"], sg(values), sg(bootstrap),
        clip_rho=clip_rho, clip_c=clip_c,
    )
    pg = -jnp.mean(logp * adv)
    value = 0.5 * jnp.mean(jnp.square(vs - values))
    ent = jnp.mean(-jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
    return pg + value_cost * value - entropy_cost * ent, logp


# -------------------------------------------------------------- optimizer


def clip_by_global_norm(grads, max_norm):
    if not max_norm:
        return grads
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def opt_init(spec: dict, params):
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
    if spec["name"] == "adam":
        return {"count": jnp.zeros((), jnp.int32), "mu": zeros(), "nu": zeros()}
    if spec["name"] == "rmsprop":
        return {"nu": zeros()}
    raise ValueError(f"no reference for optimizer {spec['name']!r}")


def opt_step(spec: dict, params, state, grads):
    """One step of the optimizer the configuration states, in float32
    arithmetic; parameters and moments are stored back in their own dtypes
    (the configuration states those).  Returns (params, state, the
    gradient as the optimizer took it)."""
    g = clip_by_global_norm(grads, spec.get("clip_norm", 0.0))
    lr = spec["lr"]
    store = lambda new, old: new.astype(old.dtype)
    if spec["name"] == "adam":
        b1, b2, eps = spec["b1"], spec["b2"], spec["eps"]
        count = state["count"] + 1
        mu = jax.tree.map(
            lambda m, x: store(b1 * m.astype(F32) + (1 - b1) * x, m),
            state["mu"], g)
        nu = jax.tree.map(
            lambda v, x: store(b2 * v.astype(F32) + (1 - b2) * x * x, v),
            state["nu"], g)
        bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
        params = jax.tree.map(
            lambda p, m, v: store(
                p.astype(F32) - lr * (m.astype(F32) / bc1)
                / (jnp.sqrt(v.astype(F32) / bc2) + eps), p),
            params, mu, nu)
        return params, {"count": count, "mu": mu, "nu": nu}, g
    decay, eps = spec["decay"], spec["eps"]
    nu = jax.tree.map(
        lambda v, x: store(decay * v.astype(F32) + (1 - decay) * x * x, v),
        state["nu"], g)
    params = jax.tree.map(
        lambda p, x, v: store(
            p.astype(F32) - lr * x / (jnp.sqrt(v.astype(F32)) + eps), p),
        params, g, nu)
    return params, {"nu": nu}, g


# --------------------------------------------------------------- readings


def worst_leaf_gap(got: dict, want: dict, keep=None) -> tuple[float, str]:
    """max over leaves of | |got| - |want| | / max(|want|, median |want|):
    the gap between the program's norm and the reference's, per leaf,
    against the larger of that leaf's norm and the median leaf's."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    worst, at = 0.0, ""
    for k in keys:
        gap = abs(got[k] - want[k]) / max(want[k], med, 1e-30)
        if not math.isfinite(gap):
            gap = float("inf")
        if gap > worst:
            worst, at = gap, k
    return worst, at


def median_leaf_gap(got: dict, want: dict, keep=None) -> float:
    """The median over leaves of the same per-leaf gap: steady from seed
    to seed where the worst leaf is one small leaf's rounding."""
    return median_leaf_ratio({k: abs(got[k] - want[k]) for k in want},
                             want, keep)


def median_leaf_ratio(num: dict, want: dict, keep=None) -> float:
    """The median over leaves of num / max(|want|, median |want|): with
    ``num`` the norm of a leaf's difference from the reference (its
    signed change less the reference's), the median leaf's distance from
    the reference against the reference's own norm."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    g = float(np.median([num[k] / max(want[k], med, 1e-30) for k in keys]))
    return g if math.isfinite(g) else float("inf")


def moved_leaves(grad_norms: dict) -> set:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's.  The others move under Adam by round-off alone and are left
    out of the parameters' change."""
    med = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v >= 1e-3 * med}


def abs_gap(got: float, want: float) -> float:
    g = abs(float(got) - float(want))
    return g if math.isfinite(g) else float("inf")
