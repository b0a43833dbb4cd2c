"""A device environment's k newest frames, stacked on the channel axis, as
the Atari agents of DQN and IMPALA see them.

``FrameStack(env, k)`` keeps the ``repro.api.DeviceEnv`` contract (pure
``init``/``observe``/``step``, auto-reset inside ``step``), so it runs
inside the fused env+act step of a device-env fleet.  Its observation is
``(H, W, k * C)``: channel block j (channels ``j*C .. (j+1)*C - 1``) holds
the inner env's frame from j steps back, block 0 the newest.  An episode's
first frame, at ``init`` and at every auto-reset (``TimeStep.first``),
fills all k blocks, as the Atari wrappers repeat the reset frame; no
block ever shows a frame of an earlier episode.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class FrameStackState(NamedTuple):
    env: Any  # the inner env's state
    past: jax.Array  # (H, W, (k - 1) * C): the k - 1 frames before the newest


class FrameStack:
    """The state keeps the older frames apart from the inner env's, and
    each observation is a fresh array: the fused actor step donates the
    env state, and an observation that shared a buffer with it would be
    donated under the agent."""

    def __init__(self, env, k: int):
        h, w, c = env.obs_shape
        self.env, self.k = env, k
        self.num_actions = env.num_actions
        self.obs_shape = (h, w, k * c)

    def _fill(self, frame: jax.Array) -> jax.Array:
        return jnp.tile(frame, (1, 1, self.k - 1))

    def init(self, rng: jax.Array) -> FrameStackState:
        s = self.env.init(rng)
        return FrameStackState(s, self._fill(self.env.observe(s)))

    def observe(self, state: FrameStackState) -> jax.Array:
        return jnp.concatenate([self.env.observe(state.env), state.past], -1)

    def step(self, state: FrameStackState, action: jax.Array):
        s, ts = self.env.step(state.env, action)
        newest = self.env.observe(state.env)  # the frame acted on
        past = jnp.concatenate([newest, state.past], -1)
        past = past[..., : state.past.shape[-1]]
        past = jnp.where(ts.first, self._fill(ts.obs), past)
        obs = jnp.concatenate([ts.obs, past], -1)
        return FrameStackState(s, past), ts._replace(obs=obs)
