"""The IMPALA "deep" network (Espeholt et al. 2018, Fig. 3) on 84x84
frames: the weights the benchmark makes, the plain float32 reference of
its forward pass and V-trace loss, and its operations per frame, from its
shapes.

Three stages of (3x3 conv, 3x3/2 max-pool, two residual blocks of two 3x3
convs), a ReLU'd dense layer, and linear policy and value heads.  The
reference imports nothing of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import reference as ref

F32 = jnp.float32


def _stages(cfg: dict):
    """(stage, in_channels, out_channels, conv_hw, pooled_hw) per stage."""
    h, w, c = cfg["frame_height"], cfg["frame_width"], cfg["frame_channels"]
    for i, ch in enumerate(cfg["channels"]):
        ph, pw = -(-h // 2), -(-w // 2)
        yield i, c, ch, (h, w), (ph, pw)
        c, h, w = ch, ph, pw


def param_shapes(cfg: dict) -> dict:
    out = {}
    for i, cin, ch, _, pooled in _stages(cfg):
        out[(f"conv_{i}", "w")] = (3, 3, cin, ch)
        out[(f"conv_{i}", "b")] = (ch,)
        for j in range(cfg["blocks_per_stage"]):
            for k in (0, 1):
                out[(f"res_{i}_{j}_{k}", "w")] = (3, 3, ch, ch)
                out[(f"res_{i}_{j}_{k}", "b")] = (ch,)
        c, hw = ch, pooled
    flat = hw[0] * hw[1] * c
    n, A = cfg["hidden"], cfg["num_actions"]
    out[("trunk", "w")], out[("trunk", "b")] = (flat, n), (n,)
    out[("policy", "w")], out[("policy", "b")] = (n, A), (A,)
    out[("value", "w")], out[("value", "b")] = (n, 1), (1,)
    return out


def make_params(cfg: dict, key) -> dict:
    """Seeded float32 weights: std 1/sqrt(fan_in), biases about 0.  The
    policy head is not shrunk as a fresh agent's would be: a policy far
    from uniform makes a sampled action's log-prob say which action it
    was."""
    tree: dict = {}
    for i, (path, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if path[1] == "b":
            x = 0.01 * jax.random.normal(k, shape, F32)
        else:
            x = jax.random.normal(k, shape, F32) / math.sqrt(math.prod(shape[:-1]))
        tree.setdefault(path[0], {})[path[1]] = x
    return tree


# -------------------------------------------------------------- reference


def _pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )


FRAMES_PER_BLOCK = 160


def torso(params, obs, cfg: dict, num: ref.Numerics = ref.HIGHEST):
    """obs (N, H, W, C) -> trunk features (N, hidden)."""
    x = obs.astype(F32)
    for i, *_ in _stages(cfg):
        p = params[f"conv_{i}"]
        x = _pool(num.conv(x, p["w"]) + p["b"])
        for j in range(cfg["blocks_per_stage"]):
            a, b = params[f"res_{i}_{j}_0"], params[f"res_{i}_{j}_1"]
            y = num.conv(jax.nn.relu(x), a["w"]) + a["b"]
            y = num.conv(jax.nn.relu(y), b["w"]) + b["b"]
            x = x + y
    x = jax.nn.relu(x).reshape(x.shape[0], -1)
    t = params["trunk"]
    return jax.nn.relu(num.einsum("nf,fh->nh", x, t["w"]) + t["b"])


def forward(params, obs, cfg: dict, num: ref.Numerics = ref.HIGHEST):
    """obs (N, H, W, C) -> (logits (N, A), values (N,)), float32.  The
    torso runs over blocks of frames one after another, each kept for the
    backward pass only as its input, so that the reference fits at the
    cell's batch."""
    f = jax.checkpoint(functools.partial(torso, cfg=cfg, num=num))
    N = obs.shape[0]
    if N > FRAMES_PER_BLOCK and N % FRAMES_PER_BLOCK == 0:
        blocks = obs.reshape((-1, FRAMES_PER_BLOCK) + obs.shape[1:])
        x = jax.lax.map(lambda o: f(params, o), blocks).reshape(N, -1)
    else:
        x = f(params, obs)
    logits = num.einsum("nh,ha->na", x, params["policy"]["w"]) + params["policy"]["b"]
    values = num.einsum("nh,ho->no", x, params["value"]["w"])[:, 0] + params["value"]["b"][0]
    return logits, values


def loss(params, traj, cfg: dict, num: ref.Numerics = ref.HIGHEST):
    """The V-trace actor-critic loss on a block of rows -> (loss,
    log pi(a_t) (B, T))."""
    hp = cfg["loss"]
    B, T = traj["actions"].shape
    obs = traj["obs"].reshape((B * T,) + traj["obs"].shape[2:])
    logits, values = forward(params, obs, cfg, num)
    _, boot = forward(params, traj["bootstrap_obs"], cfg, num)
    return ref.impala_terms(
        logits.reshape(B, T, -1), values.reshape(B, T), boot, traj,
        entropy_cost=hp["entropy_cost"], value_cost=hp["value_cost"],
    )


# ---------------------------------------------------- operations and bytes


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def frame_flops(cfg: dict) -> float:
    """Forward operations for one frame: 2 per multiply-add of every conv
    (3x3, stride 1, same padding) and dense layer."""
    total = 0.0
    for _, cin, ch, (h, w), (ph, pw) in _stages(cfg):
        total += 2.0 * h * w * 9 * cin * ch
        total += 2.0 * ph * pw * 9 * ch * ch * 2 * cfg["blocks_per_stage"]
    for path, shape in param_shapes(cfg).items():
        if path[0] in ("trunk", "policy", "value") and path[1] == "w":
            total += 2.0 * shape[0] * shape[1]
    return total


def flops_per_frame(cfg: dict, traffic: dict) -> float:
    """Per trained frame: the actor's forward, the learner's forward and
    backward (3x forward), and the bootstrap forward shared by the T
    frames of a row."""
    return frame_flops(cfg) * (1.0 + 3.0 + 1.0 / traffic["trajectory_length"])


def act_step_cost(cfg: dict, traffic: dict) -> tuple[float, float]:
    """(operations, bytes) one act step needs at least: the forward pass
    of the actor batch, and every float32 weight and one float32 batch of
    frames read once."""
    B = traffic["actor_batch_size"]
    frame = cfg["frame_height"] * cfg["frame_width"] * cfg["frame_channels"]
    return B * frame_flops(cfg), float(4 * (param_count(cfg) + B * frame))
