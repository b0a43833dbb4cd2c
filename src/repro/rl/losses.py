"""RL losses: policy gradient, entropy, value, and the composed IMPALA
(V-trace actor-critic) and A2C objectives used by the Podracer agents.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.vtrace.ops import vtrace
from repro.rl import returns as rets


def log_prob(logits: jax.Array, actions: jax.Array) -> jax.Array:
    """logits (..., A), actions (...) -> log pi(a|s).

    Computed as logit[a] - logsumexp(logits): avoids materializing the full
    log_softmax array, which matters when A = an LLM vocabulary (§Perf).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    chosen = jnp.take_along_axis(logits, actions[..., None], axis=-1)[..., 0]
    return chosen - lse


def entropy(logits: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.sum(jnp.exp(logp) * logp, axis=-1)


def policy_gradient_loss(
    logits: jax.Array, actions: jax.Array, advantages: jax.Array
) -> jax.Array:
    adv = jax.lax.stop_gradient(advantages)
    return -jnp.mean(log_prob(logits, actions) * adv)


class ImpalaLossOut(NamedTuple):
    total: jax.Array
    pg: jax.Array
    value: jax.Array
    entropy: jax.Array
    mean_rho: jax.Array


def impala_loss(
    logits: jax.Array,  # (B, T, A) learner policy
    values: jax.Array,  # (B, T)
    actions: jax.Array,  # (B, T)
    behaviour_logp: jax.Array,  # (B, T) log mu(a|s) from the actor
    rewards: jax.Array,  # (B, T)
    discounts: jax.Array,  # (B, T)
    bootstrap_value: jax.Array,  # (B,)
    *,
    entropy_cost: float = 0.01,
    value_cost: float = 0.5,
    clip_rho: float = 1.0,
    clip_c: float = 1.0,
) -> ImpalaLossOut:
    """The V-trace actor-critic loss (Espeholt et al. 2018, eq. 1-4).

    The uniform-weight special case of ``weighted_impala_loss`` (multiplying
    by 1.0 is exact, so the numerics are bit-identical), without the
    per-sequence TD output replay mode needs.
    """
    out = weighted_impala_loss(
        logits, values, actions, behaviour_logp, rewards, discounts,
        bootstrap_value, importance_weights=None,
        entropy_cost=entropy_cost, value_cost=value_cost,
        clip_rho=clip_rho, clip_c=clip_c,
    )
    return ImpalaLossOut(
        total=out.total, pg=out.pg, value=out.value, entropy=out.entropy,
        mean_rho=out.mean_rho,
    )


def per_importance_weights(
    probs: jax.Array, size: jax.Array, beta: float | jax.Array, *,
    axis_name: str | None = None,
) -> jax.Array:
    """PER bias correction: w_i = (N * P(i))^-beta, normalized by max.

    ``probs`` are the selection probabilities returned by ``replay.sample``
    and ``size`` the number of valid slots; ``beta`` anneals toward 1 over
    training in the original recipe and may be a traced scalar
    (``ReplayConfig.importance_beta`` computes the schedule inside the
    fused off-policy step) or a fixed float.

    Inside shard_map/pmap pass ``axis_name`` so the normalization uses the
    *global* max across learner shards: a per-shard max would give
    identical-priority slots different effective weights depending on
    which shard happened to draw them, making training depend on the
    learner count.
    """
    w = (jnp.maximum(size, 1).astype(jnp.float32) * probs) ** (-beta)
    w_max = jnp.max(w)
    if axis_name is not None:
        w_max = jax.lax.pmax(w_max, axis_name)
    return w / jnp.maximum(w_max, 1e-20)


class WeightedImpalaOut(NamedTuple):
    total: jax.Array
    pg: jax.Array
    value: jax.Array
    entropy: jax.Array
    mean_rho: jax.Array
    per_seq_td: jax.Array  # (B,) |vs - V| per sequence -> replay priorities


def weighted_impala_loss(
    logits: jax.Array,  # (B, T, A) learner policy
    values: jax.Array,  # (B, T)
    actions: jax.Array,  # (B, T)
    behaviour_logp: jax.Array,  # (B, T) log mu(a|s) from the actor
    rewards: jax.Array,  # (B, T)
    discounts: jax.Array,  # (B, T)
    bootstrap_value: jax.Array,  # (B,)
    *,
    importance_weights: jax.Array | None = None,  # (B,) replay IS weights
    entropy_cost: float = 0.01,
    value_cost: float = 0.5,
    clip_rho: float = 1.0,
    clip_c: float = 1.0,
) -> WeightedImpalaOut:
    """V-trace loss with per-sequence importance weighting (off-policy
    Sebulba): V-trace's rho/c clipping corrects the actor-policy lag, while
    ``importance_weights`` corrects the *sampling* bias a prioritized replay
    distribution introduces.  Also emits per-sequence TD magnitudes, the
    priority signal written back into the replay ring after each update.
    """
    target_logp = log_prob(logits, actions)
    log_rhos = target_logp - behaviour_logp
    with jax.named_scope("vtrace"):
        vt = vtrace(
            log_rhos, discounts, rewards, values, bootstrap_value,
            clip_rho=clip_rho, clip_c=clip_c,
        )
    if importance_weights is None:
        w = jnp.ones(values.shape[:1], jnp.float32)
    else:
        w = jax.lax.stop_gradient(importance_weights)
    wn = w[:, None]
    pg = -jnp.mean(wn * target_logp * vt.pg_advantages)
    value = 0.5 * jnp.mean(wn * jnp.square(vt.vs - values))
    ent = jnp.mean(wn * entropy(logits))
    total = pg + value_cost * value - entropy_cost * ent
    per_seq_td = jnp.mean(
        jnp.abs(jax.lax.stop_gradient(vt.vs) - values), axis=1
    )
    return WeightedImpalaOut(
        total=total, pg=pg, value=value, entropy=ent,
        mean_rho=jnp.mean(jnp.exp(log_rhos)),
        per_seq_td=jax.lax.stop_gradient(per_seq_td),
    )


class PPOLossOut(NamedTuple):
    total: jax.Array
    pg: jax.Array
    value: jax.Array
    entropy: jax.Array
    clip_frac: jax.Array


def ppo_loss(
    logits: jax.Array,  # (B, T, A)
    values: jax.Array,  # (B, T)
    actions: jax.Array,  # (B, T)
    behaviour_logp: jax.Array,  # (B, T)
    rewards: jax.Array,
    discounts: jax.Array,
    bootstrap_value: jax.Array,
    *,
    clip_eps: float = 0.2,
    gae_lambda: float = 0.95,
    entropy_cost: float = 0.01,
    value_cost: float = 0.5,
) -> PPOLossOut:
    """Clipped-surrogate PPO with GAE advantages (Schulman et al. 2017).

    In Anakin's fused loop this runs one epoch per on-policy batch; in
    Sebulba the behaviour_logp comes from the (slightly stale) actor
    policy, so the ratio clip doubles as off-policy protection.
    """
    from repro.rl import returns as rets

    adv, targets = rets.gae(
        rewards, discounts, values, bootstrap_value, lambda_=gae_lambda
    )
    adv = jax.lax.stop_gradient(
        (adv - adv.mean()) / jnp.maximum(adv.std(), 1e-6)
    )
    targets = jax.lax.stop_gradient(targets)
    logp = log_prob(logits, actions)
    ratio = jnp.exp(logp - behaviour_logp)
    clipped = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps)
    pg = -jnp.mean(jnp.minimum(ratio * adv, clipped * adv))
    value = 0.5 * jnp.mean(jnp.square(targets - values))
    ent = jnp.mean(entropy(logits))
    total = pg + value_cost * value - entropy_cost * ent
    return PPOLossOut(
        total=total, pg=pg, value=value, entropy=ent,
        clip_frac=jnp.mean((jnp.abs(ratio - 1) > clip_eps).astype(jnp.float32)),
    )


class A2CLossOut(NamedTuple):
    total: jax.Array
    pg: jax.Array
    value: jax.Array
    entropy: jax.Array


def a2c_loss(
    logits: jax.Array,
    values: jax.Array,
    actions: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    bootstrap_value: jax.Array,
    *,
    entropy_cost: float = 0.01,
    value_cost: float = 0.5,
    td_lambda: float = 1.0,
) -> A2CLossOut:
    """On-policy advantage actor-critic (the Anakin agent objective)."""
    values_tp1 = jnp.concatenate([values[:, 1:], bootstrap_value[:, None]], axis=1)
    targets = rets.lambda_returns(rewards, discounts, values_tp1, td_lambda)
    targets = jax.lax.stop_gradient(targets)
    adv = targets - values
    pg = policy_gradient_loss(logits, actions, adv)
    value = 0.5 * jnp.mean(jnp.square(targets - values))
    ent = jnp.mean(entropy(logits))
    total = pg + value_cost * value - entropy_cost * ent
    return A2CLossOut(total=total, pg=pg, value=value, entropy=ent)
