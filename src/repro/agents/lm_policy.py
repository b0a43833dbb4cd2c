"""LM policy as a first-class Podracer agent (ISSUE 9 tentpole).

``LMPolicyAgent`` puts a model-zoo transformer (repro/configs/) on the
Sebulba dataflow with **zero changes to core/sebulba.py**: autoregressive
generation *is* the ``act()`` hot loop, and the decode state — KV cache
plus position counter — *is* the declared recurrent carry.  Everything the
runner already does for recurrent agents (thread the carry through the
fused donated act-step, episode-reset it where ``discount == 0``)
therefore applies to LM rollouts for free.  The loss never reads
``Trajectory.init_carry`` (trajectories are whole episodes from the zero
carry), and the agents declare so (``AgentSpec.reads_init_carry=False``):
the runner stores no snapshot of the decode state, which for a Mamba-2
model is megabytes a row.  This is the
RLAX architecture (PAPERS.md) expressed on our stack.

Carry layout (the contract tests/test_lm_policy.py pins):

  * the model is built with ``unroll=True``, which forces the looped
    per-layer cache layout whose every leaf is **batch-leading** —
    ``{"layer_i": {"k": (B, S, K, h), "v": ...}}`` for attention,
    ``{"ssm": (B, H, P, N), "conv": (B, W-1, C)}`` for ssm blocks.  The
    stacked layout is layers-leading ``(L, B, ...)`` and would break both
    the runner's episode-reset broadcast and ``split_for_learners``;
  * ``carry["pos"]`` is a per-row ``(B,)`` int32 step counter.  It is
    all-zero at init (so ``resolve_agent``'s zero-carry check passes) and
    reset to zero with the rest of the carry at episode boundaries.

Decode position: ``model.decode_step`` takes one *scalar* position (one
rope offset, one cache write index for the whole batch), so ``act`` uses
``max(carry["pos"])`` under a **lockstep invariant**: every fleet row
starts at t == 0 and ``TokenEnv`` episodes are fixed-length, so per-row
positions never diverge.  (A scenario mix of different episode lengths
would violate this — pair LM agents with equal-length token tasks.)

Inside ``decode_step`` the attention hot loop runs behind the
``flash_decode`` kernel wrapper (see models/transformer.py): the Pallas
kernel on TPU, its bit-identical jnp oracle elsewhere.

``loss()`` is the V-trace-corrected LM objective from ``launch/steps.py``:
one full causal forward over ``[obs, bootstrap_obs]`` (prefill teacher-
forcing the tokens the actor generated), next-token cross-entropy on that
sequence, plus the IMPALA V-trace actor-critic term in which stale
generations are importance-weighted via rho/c clipping against the stored
``behaviour_logp``.  The forward's position-t logits are conditioned on
obs <= t, exactly matching the actor's KV-cache conditioning when
trajectory slices are episode-aligned — so configure
``trajectory_length == env.episode_len`` (drains and episodes both start
at step 0, so they stay in phase).

``LMReplayPolicyAgent`` additionally declares ``replay=True``: PER
importance weights scale both the CE and RL terms per sequence, and
per-sequence TD magnitudes flow back as replay priorities.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.api import ActAux, AgentSpec, LossAux
from repro.launch.steps import TrainHParams
from repro.models.model import make_model
from repro.rl import losses


class LMPolicyAgent:
    """Autoregressive token policy on the ``repro.api`` agent contract.

    ``cfg`` is an ``ArchConfig`` from the model zoo; ``max_seq`` the cache
    capacity in tokens — at least the env's episode length, since the
    position counter only rewinds at episode resets.  ``hparams`` follows
    ``launch.steps.TrainHParams`` (CE + rl_weight * V-trace + aux).
    """

    spec = AgentSpec(recurrent=True, reads_init_carry=False)

    def __init__(self, cfg, *, max_seq: int, hparams: TrainHParams | None = None,
                 cache_dtype=None):
        self.cfg = cfg
        self.max_seq = int(max_seq)
        self.hp = hparams or TrainHParams()
        self.cache_dtype = cache_dtype
        # unroll=True -> looped per-layer params and the batch-leading
        # cache layout the Sebulba carry protocol requires (see module
        # docstring); params layout must match, hence set here once.
        self.model = make_model(cfg, unroll=True)

    # -- actor ------------------------------------------------------------

    def init(self, rng, obs_shape):
        """Token observations are scalar — obs_shape is accepted for the
        runner contract but carries no information."""
        return self.model.init(rng)

    def initial_carry(self, batch: int):
        """Zero-valued (NOT empty-shaped) decode state: zeroed KV cache +
        zeroed position counter.  Episode resets restore exactly this."""
        cache, _ = self.model.init_cache(
            batch, self.max_seq, dtype=self.cache_dtype
        )
        return {"cache": cache, "pos": jnp.zeros((batch,), jnp.int32)}

    def act(self, params, obs, rng, carry):
        """One autoregressive decode step: obs (B,) int32 tokens ->
        (sampled tokens (B,), ActAux(logp), advanced decode state).

        Runs inside Sebulba's fused donated act-step; the carry arriving
        here is already episode-reset, so ``pos`` is 0 exactly when the
        cache is freshly zeroed.
        """
        tokens = obs.astype(jnp.int32).reshape(-1, 1)
        # scalar decode position from the per-row counters (lockstep
        # invariant — see module docstring)
        pos = jnp.max(carry["pos"])
        logits, _, cache = self.model.decode_step(
            params, carry["cache"], tokens, pos
        )
        with jax.named_scope("sampling"):
            logits = logits[:, 0].astype(jnp.float32)
            actions = jax.random.categorical(rng, logits)
            logp = losses.log_prob(logits, actions)
        return actions, ActAux(logp), {"cache": cache, "pos": carry["pos"] + 1}

    # -- learner ----------------------------------------------------------

    def _objective(self, params, traj, weights):
        """Shared CE + V-trace objective -> (total, metrics, vtrace out)."""
        hp = self.hp
        B, T = traj.actions.shape
        # teacher-force the generated episode in one causal prefill; the
        # trailing bootstrap obs supplies both the last CE target and the
        # bootstrap value (V-trace scales it by the terminal discount).
        tokens = jnp.concatenate(
            [traj.obs.astype(jnp.int32),
             traj.bootstrap_obs.astype(jnp.int32)[:, None]], axis=1,
        )
        logits, values, aux = self.model.forward(params, {"tokens": tokens})
        logits_t = logits[:, :T]  # position t conditioned on obs <= t
        values_t = values[:, :T]
        # next-token CE over the rollout (launch/steps.py make_loss_fn)
        lse = jax.nn.logsumexp(logits_t, axis=-1)
        tgt = jnp.take_along_axis(
            logits_t, tokens[:, 1:][..., None], axis=-1
        )[..., 0]
        ce_seq = jnp.mean(lse - tgt, axis=1)  # (B,)
        if weights is None:
            ce = jnp.mean(ce_seq)
        else:
            ce = jnp.mean(ce_seq * weights)
        out = losses.weighted_impala_loss(
            logits_t, values_t, traj.actions, traj.behaviour_logp,
            traj.rewards, traj.discounts, values[:, T],
            importance_weights=weights,
            entropy_cost=hp.entropy_cost, value_cost=hp.value_cost,
        )
        total = ce + hp.rl_weight * out.total + hp.aux_weight * aux
        metrics = {
            "loss": total, "ce": ce, "rl": out.total,
            "aux": jnp.asarray(aux, jnp.float32), "entropy": out.entropy,
        }
        return total, metrics, out

    def loss(self, params, traj, weights=None):
        if weights is not None:
            raise ValueError(
                "LMPolicyAgent is on-policy (AgentSpec.replay=False) and "
                "does not apply importance weights; use LMReplayPolicyAgent "
                "for PER-weighted replay losses"
            )
        total, metrics, _ = self._objective(params, traj, None)
        return total, LossAux(metrics)


class LMReplayPolicyAgent(LMPolicyAgent):
    """Off-policy LM agent: PER importance weights in (scaling CE and the
    V-trace term per sequence), per-sequence TD priorities out — stale
    generations replay with both corrections RLAX prescribes."""

    spec = AgentSpec(recurrent=True, replay=True, reads_init_carry=False)

    def loss(self, params, traj, weights=None):
        total, metrics, out = self._objective(params, traj, weights)
        return total, LossAux(metrics, out.per_seq_td)
