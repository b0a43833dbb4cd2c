"""The frame stack against the inner env stepped by hand: the stacked
shape, the fill with an episode's first frame, channel block j as the
frame from j steps back, and the same under jit and vmap through the
device-env fleet that the fused actor step runs."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.envs import DeviceEnvFleet, Pong

from bench.frame_stack import FrameStack

K = 4


def small_pong():
    # one life on a short board: an episode ends every few steps
    return Pong(height=5, width=7, max_lives=1)


def inner_frames(env, rng, actions):
    """The inner env's frames, eagerly: [first frame, after step 1, ...]
    and whether each opens an episode."""
    s = env.init(rng)
    frames, first = [np.asarray(env.observe(s))], [True]
    for a in actions:
        s, ts = env.step(s, jnp.int32(a))
        frames.append(np.asarray(ts.obs))
        first.append(bool(ts.first))
    return frames, first


def expected_stack(frames, first, t):
    """Block j is the frame from j steps back, or the episode's first
    frame where the episode is younger than j steps."""
    start = max(i for i in range(t + 1) if first[i])
    return np.concatenate([frames[max(t - j, start)] for j in range(K)], -1)


def test_shapes_and_contract():
    env = FrameStack(Pong(height=84, width=84), K)
    assert env.obs_shape == (84, 84, 4)
    assert env.num_actions == 3
    s = env.init(jax.random.key(0))
    assert env.observe(s).shape == env.obs_shape
    s, ts = env.step(s, jnp.int32(1))
    assert ts.obs.shape == env.obs_shape and ts.obs.dtype == jnp.float32
    assert ts.reward.shape == ts.discount.shape == ()


def test_reset_fills_the_stack_with_the_first_frame():
    env = FrameStack(small_pong(), K)
    s = env.init(jax.random.key(1))
    frame = np.asarray(env.env.observe(s.env))
    np.testing.assert_array_equal(env.observe(s), np.tile(frame, (1, 1, K)))
    resets = 0
    for _ in range(40):
        s, ts = env.step(s, jnp.int32(0))
        if bool(ts.first):
            resets += 1
            block = np.asarray(ts.obs[..., :1])
            np.testing.assert_array_equal(ts.obs, np.tile(block, (1, 1, K)))
    assert resets >= 2


def test_block_j_is_the_frame_from_j_steps_back():
    inner = small_pong()
    env = FrameStack(inner, K)
    rng = jax.random.key(2)
    actions = np.random.default_rng(0).integers(0, 3, 60)
    frames, first = inner_frames(inner, rng, actions)
    assert sum(first[1:]) >= 2  # episodes end inside the run
    s = env.init(rng)
    np.testing.assert_array_equal(env.observe(s), expected_stack(frames, first, 0))
    for t, a in enumerate(actions, start=1):
        s, ts = env.step(s, jnp.int32(a))
        np.testing.assert_array_equal(ts.obs, expected_stack(frames, first, t))


def test_runs_under_jit_and_vmap_in_the_fleet():
    inner = small_pong()
    fleet = DeviceEnvFleet(FrameStack(inner, K), 6, shards=3)
    assert fleet.obs_shape == (5, 7, K)
    rng = jax.random.key(3)
    actions = np.random.default_rng(1).integers(0, 3, (30, 6))
    state = jax.jit(fleet.init)(rng)
    step = jax.jit(fleet.step)
    got = [np.asarray(fleet.observe(state))]
    for a in actions:
        state, ts = step(state, jnp.asarray(a, jnp.int32))
        got.append(np.asarray(ts.obs))
    keys = jax.random.split(rng, 6)
    for row in range(6):
        frames, first = inner_frames(inner, keys[row], actions[:, row])
        for t in range(len(actions) + 1):
            np.testing.assert_array_equal(got[t][row],
                                          expected_stack(frames, first, t))
