"""The plain float32 references against the program, at a small size on
the CPU (where the program's matrix products run in float32 and its
kernels run their jnp oracles): the qwen2 forward against
``model.forward``, each cell's loss against its agent's ``loss``, and the
reference's one-device update against the program's update over three
learner devices."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as ref
from bench.drivers import sebulba as drv


def f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def lm_program(cell):
    parts, _ = cell.cfg_module.program_parts(cell.cfg, cell.traffic)
    agent = parts["agent"]
    arch = dataclasses.replace(agent.cfg, param_dtype="float32")
    agent.cfg = arch
    from repro.models.model import make_model

    agent.model = make_model(arch, unroll=True)
    return agent


def random_traj(rng, B, T, obs, actions):
    k = jax.random.split(rng, 6)
    return {
        "obs": obs(k[0], (B, T)),
        "actions": jax.random.randint(k[1], (B, T), 0, actions),
        "rewards": jax.random.normal(k[2], (B, T)),
        "discounts": 0.99 * (jax.random.uniform(k[3], (B, T)) > 0.1),
        "behaviour_logp": -jax.random.uniform(k[4], (B, T), minval=0.5, maxval=3.0),
        "bootstrap_obs": obs(k[5], (B,)),
    }


def as_program_traj(t):
    from repro.data.trajectory import Trajectory

    return Trajectory(**{k: t[k] for k in drv.TRAJ_FIELDS})


def test_qwen2_forward_matches_program(tiny_cell):
    cell = tiny_cell("lmrl-qwen2-copy64")
    agent = lm_program(cell)
    p = f32(drv.make_params(cell, 5))
    tokens = jax.random.randint(jax.random.key(1), (3, 11), 0, cell.cfg["vocab_size"])
    mod = cell.cfg_module
    want_l, want_v = mod.forward(mod.to_reference(p), tokens, cell.cfg)
    got_l, got_v, _ = agent.model.forward(p, {"tokens": tokens})
    np.testing.assert_allclose(got_l, want_l, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got_v, want_v, rtol=2e-4, atol=2e-4)


def test_qwen2_loss_matches_program(tiny_cell):
    cell = tiny_cell("lmrl-qwen2-copy64")
    agent = lm_program(cell)
    p = f32(drv.make_params(cell, 6))
    V = cell.cfg["vocab_size"]
    tok = lambda k, s: jax.random.randint(k, s, 0, V)
    t = random_traj(jax.random.key(2), 4, 8, tok, V)
    mod = cell.cfg_module
    want, _ = mod.loss(mod.to_reference(p), t, cell.cfg)
    got, _ = agent.loss(p, as_program_traj(t))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    gw = jax.grad(lambda q: mod.loss(q, t, cell.cfg)[0])(mod.to_reference(p))
    gg = mod.to_reference(
        jax.grad(lambda q: agent.loss(q, as_program_traj(t))[0])(p))
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6)


def impala_program(cell, devices):
    parts, loss_kw = cell.cfg_module.program_parts(cell.cfg, cell.traffic)
    from repro.core.sebulba import Sebulba, SebulbaConfig

    return Sebulba(
        optimizer=drv.make_optimizer(cell.cfg["optimizer"]),
        config=SebulbaConfig(
            num_actor_cores=1, threads_per_actor_core=1,
            actor_batch_size=cell.traffic["actor_batch_size"],
            trajectory_length=cell.traffic["trajectory_length"], **loss_kw),
        devices=devices, **parts)


def pong_traj(cell, rng):
    c = cell.cfg
    frame = (c["frame_height"], c["frame_width"], c["frame_channels"])
    obs = lambda k, s: (jax.random.uniform(k, s + frame) > 0.97).astype(jnp.float32)
    return random_traj(rng, cell.traffic["actor_batch_size"],
                       cell.traffic["trajectory_length"], obs, c["num_actions"])


def test_impala_loss_matches_program(tiny_cell):
    cell = tiny_cell("impala-deep-pong84-x4")
    seb = impala_program(cell, jax.devices()[:1])
    p = drv.make_params(cell, 7)
    t = pong_traj(cell, jax.random.key(3))
    want, logp = cell.cfg_module.loss(p, t, cell.cfg)
    got, _ = seb.agent.loss(p, as_program_traj(t))
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-7)
    logits, _ = seb.agent.net.apply(p, t["obs"][:, 0])
    want_logits, _ = cell.cfg_module.forward(p, t["obs"][:, 0], cell.cfg)
    np.testing.assert_allclose(logits, want_logits, rtol=1e-5, atol=1e-6)


def test_one_device_update_matches_three_learners(tiny_cell):
    """The reference's update on one device over the whole batch against
    the program's shard_map update over three learner devices, each on a
    third of the batch with the gradients averaged."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cell = tiny_cell("impala-deep-pong84-x4")
    seb = impala_program(cell, jax.devices()[:4])
    assert seb.L == 3
    p0 = drv.make_params(cell, 8)
    t = pong_traj(cell, jax.random.key(4))
    spec = cell.cfg["optimizer"]
    g = jax.grad(lambda q: cell.cfg_module.loss(q, t, cell.cfg)[0])(p0)
    want, _, _ = ref.opt_step(spec, p0, ref.opt_init(spec, p0), g)

    rep = NamedSharding(seb.learner_mesh, P())
    params = jax.device_put(p0, rep)
    opt_state = jax.device_put(seb.opt.init(params), rep)
    traj = jax.device_put(as_program_traj(t),
                          NamedSharding(seb.learner_mesh, P("batch")))
    got, _, _ = jax.jit(seb._build_update(traj))(params, opt_state, traj)
    for a, b, c in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(p0)):
        np.testing.assert_allclose(np.asarray(a) - c, np.asarray(b) - c,
                                   rtol=1e-3, atol=1e-9)


def test_fp8_control_rounds_operands():
    x = jnp.linspace(-3.0, 3.0, 1001)
    q = ref.FP8.cast(x)
    assert float(jnp.max(jnp.abs(q - x))) > 1e-3  # three mantissa bits
    assert float(jnp.max(jnp.abs(q - x) / jnp.maximum(jnp.abs(x), 0.05))) < 0.07
    assert float(jnp.max(jnp.abs(ref.HIGHEST.cast(x) - x))) == 0.0
