"""granite-4.0-h-micro: Mamba-2 and attention layers in one dense stack.

At a small Granite-shaped size (two Mamba-2 layers around one attention
layer, every Granite scalar as published) on the CPU, against a plain
float32 reference written here from the published equations, with no
kernel, cache or chunking:

  * the forward pass's logits and values;
  * decoding token by token through the carry (the SSM state and conv
    window of the Mamba-2 layers, the keys and values of the attention
    layer) equals the forward pass at every position, logits compared;
    a decode that drops the SSM state between steps fails that comparison;
  * the LM loss's gradient through every layer;
  * the SSD scan's VJP, jnp and Pallas (interpret mode), against
    ``jax.grad`` of the sequential recurrence at lengths that are not a
    whole number of chunks;
  * the mixers run under named scopes of their kind;
  * the runner stores no starting carry for the LM agents, and still does
    for the recurrent IMPALA (R2D2) agent.

Tolerances: the program and the reference both compute in float32 here,
and differ only in the order of their sums (the SSD's chunked form against
the recurrence, the attention's online softmax against a plain softmax),
so they agree to a few float32 roundings of the largest term.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.agents.lm_policy import LMPolicyAgent
from repro.configs import granite_4_0_h_micro as granite
from repro.core.sebulba import Sebulba, SebulbaConfig
from repro.envs import TokenEnv
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.launch.steps import TrainHParams
from repro.models.model import make_model

# float32 sums in two orders over a few hundred terms of size <= 1
ATOL, RTOL = 2e-5, 2e-4


def small_cfg(**kw):
    return dataclasses.replace(granite.reduced(), param_dtype="float32",
                               cache_dtype="float32", remat="none", **kw)


def model_and_params(seed=0):
    model = make_model(small_cfg(), unroll=True)
    params = model.init(jax.random.key(seed))
    # the default init leaves the SSM's per-head scalars constant: give
    # every head its own decay, step and skip, as trained weights would
    keys = iter(jax.random.split(jax.random.key(seed + 100), 64))
    for name, p in params.items():
        if name.startswith("layer_") and "mixer" in p:
            m = p["mixer"]
            H = m["A_log"].shape[0]
            m["A_log"] = jnp.log(jax.random.uniform(next(keys), (H,), minval=1, maxval=4))
            m["dt_bias"] = jax.random.normal(next(keys), (H,))
            m["D"] = jax.random.normal(next(keys), (H,))
            m["conv_b"] = 0.1 * jax.random.normal(next(keys), m["conv_b"].shape)
    return model, params


# ---------------------------------------------------------------- reference


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _ssd_recurrence(x, dt, A, Bm, Cm):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t, from the
    zero state, one step at a time.  x (B, T, H, P), dt (B, T, H), A (H,),
    Bm and Cm (B, T, N) -> (y, final state)."""
    Bsz, _, H, P = x.shape
    S = jnp.zeros((Bsz, H, P, Bm.shape[-1]), jnp.float32)
    ys = []
    for t in range(x.shape[1]):
        decay = jnp.exp(dt[:, t] * A)[..., None, None]
        S = decay * S + (x[:, t] * dt[:, t, :, None])[..., None] * Bm[:, t, None, None, :]
        ys.append(jnp.sum(S * Cm[:, t, None, None, :], -1))
    return jnp.stack(ys, 1), S


def _mamba2(m, x, cfg):
    B, T, _ = x.shape
    di, N, H, P, W = (cfg.d_inner, cfg.ssm_state, cfg.ssm_heads,
                      cfg.ssm_head_dim, cfg.conv_width)
    h = _rms(x, m["norm"]["scale"], cfg.rms_norm_eps)
    proj = h @ m["in_proj"]
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], proj[..., 2 * di + 2 * N:]
    pad = jnp.concatenate([jnp.zeros((B, W - 1, xbc.shape[-1])), xbc], 1)
    conv = sum(pad[:, i:i + T] * m["conv_w"][i] for i in range(W)) + m["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :di].reshape(B, T, H, P)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    y, _ = _ssd_recurrence(xs, dt, -jnp.exp(m["A_log"]), xbc[..., di:di + N],
                           xbc[..., di + N:])
    y = (y + m["D"][:, None] * xs).reshape(B, T, di) * jax.nn.silu(z)
    return _rms(y, m["out_norm"]["scale"], cfg.rms_norm_eps) @ m["out_proj"]


def _attention(p, x, cfg):
    B, T, _ = x.shape
    K, G, hd = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim
    h = _rms(x, p["attn_norm"]["scale"], cfg.rms_norm_eps)
    q = jnp.einsum("btd,dnh->btnh", h, p["attn"]["wq"]).reshape(B, T, K, G, hd)
    k = jnp.einsum("btd,dnh->btnh", h, p["attn"]["wk"])
    v = jnp.einsum("btd,dnh->btnh", h, p["attn"]["wv"])
    s = jnp.einsum("btkgh,bskh->bkgts", q, k) * cfg.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bkgts,bskh->btkgh", jax.nn.softmax(s, -1), v)
    return jnp.einsum("btnh,nhd->btd", o.reshape(B, T, -1, hd), p["attn"]["wo"])


def reference_forward(params, tokens, cfg):
    """x0 = m_e E[tok]; per layer x += r mixer(RMSNorm(x)), x += r
    MLP(RMSNorm(x)); logits = RMSNorm(x) E^T / s, values from the head."""
    r = cfg.residual_multiplier
    x = params["embedding"]["table"][tokens] * cfg.embedding_multiplier
    for i, kind in enumerate(cfg.layer_types):
        p = params[f"layer_{i}"]
        mix = _mamba2(p["mixer"], x, cfg) if kind == "mamba" else _attention(p, x, cfg)
        x = x + r * mix
        h = _rms(x, p["ffn_norm"]["scale"], cfg.rms_norm_eps)
        m = p["mlp"]
        x = x + r * ((jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"])
    x = _rms(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
    logits = x @ params["embedding"]["table"].T / cfg.logits_scaling
    return logits, (x @ params["value_head"]["w"])[..., 0]


# -------------------------------------------------------------------- model


def test_forward_matches_reference():
    model, params = model_and_params()
    cfg = model.cfg
    assert cfg.layer_types == ("mamba", "attention", "mamba")
    tokens = jax.random.randint(jax.random.key(1), (2, 13), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want_l, want_v = reference_forward(params, tokens, cfg)
        got_l, got_v, _ = jax.jit(model.forward)(params, {"tokens": tokens})
    scale = float(jnp.max(jnp.abs(want_l)))
    assert scale > 1e-2  # the comparison is not of near-zero logits
    np.testing.assert_allclose(got_l, want_l, atol=ATOL * scale, rtol=RTOL)
    np.testing.assert_allclose(got_v, want_v, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("step", ["forward", "decode_step"])
def test_mixers_run_under_scopes_of_their_kind(step):
    """The learner's forward and the act step's decode put each Mamba-2
    mixer under a ``mamba2`` scope and the attention under ``attention``,
    the names a device profile attributes their ops by."""
    model, params = model_and_params()
    tokens = jnp.zeros((2, 5), jnp.int32)
    if step == "forward":
        lowered = jax.jit(model.forward).lower(params, {"tokens": tokens})
    else:
        cache, _ = model.init_cache(2, 5)
        lowered = jax.jit(model.decode_step).lower(
            params, cache, tokens[:, :1], jnp.int32(0))
    text = lowered.as_text(debug_info=True)
    assert "/mamba2/" in text and "/attention/" in text


def _decode_all(model, params, tokens, drop_ssm=False):
    B, T = tokens.shape
    cache, _ = model.init_cache(B, T)
    step = jax.jit(model.decode_step)
    out = []
    for t in range(T):
        if drop_ssm:
            cache = {k: ({**c, "ssm": jnp.zeros_like(c["ssm"])} if "ssm" in c else c)
                     for k, c in cache.items()}
        lg, _, cache = step(params, cache, tokens[:, t:t + 1], jnp.int32(t))
        out.append(lg[:, 0])
    return jnp.stack(out, 1), cache


def test_decode_through_the_carry_matches_forward():
    model, params = model_and_params(seed=3)
    cfg = model.cfg
    tokens = jax.random.randint(jax.random.key(4), (2, 11), 0, cfg.vocab_size)
    want, _, _ = model.forward(params, {"tokens": tokens})
    got, cache = _decode_all(model, params, tokens)
    # the carry: Mamba-2 state in float32 and the conv window, KV for
    # the attention layer, batch-leading
    assert set(cache["layer_0"]) == {"ssm", "conv"}
    assert cache["layer_0"]["ssm"].dtype == jnp.float32
    assert set(cache["layer_1"]) == {"k", "v"}
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=ATOL * scale, rtol=RTOL)


def test_decode_that_drops_the_ssm_state_is_caught():
    model, params = model_and_params(seed=3)
    tokens = jax.random.randint(jax.random.key(4), (2, 11), 0,
                                model.cfg.vocab_size)
    want, _, _ = model.forward(params, {"tokens": tokens})
    got, _ = _decode_all(model, params, tokens, drop_ssm=True)
    scale = float(jnp.max(jnp.abs(want)))
    gap = float(jnp.max(jnp.abs(got - want)))
    # the first step is exact either way (the state is still zero)
    np.testing.assert_allclose(got[:, 0], want[:, 0], atol=ATOL * scale, rtol=RTOL)
    assert gap > 100 * ATOL * scale, gap


def test_lm_loss_gradient_matches_reference():
    """The LM policy agent's loss, cross-entropy alone (rl_weight 0),
    differentiated through every layer, SSD scan included."""
    cfg = small_cfg()
    agent = LMPolicyAgent(cfg, max_seq=8, hparams=TrainHParams(
        rl_weight=0.0, aux_weight=0.0))
    _, params = model_and_params(seed=5)
    from repro.data.trajectory import Trajectory

    B, T = 3, 8
    k = jax.random.split(jax.random.key(6), 2)
    obs = jax.random.randint(k[0], (B, T), 0, cfg.vocab_size)
    boot = jax.random.randint(k[1], (B,), 0, cfg.vocab_size)
    z = jnp.zeros((B, T))
    traj = Trajectory(obs=obs, actions=obs, rewards=z, discounts=z + 1,
                      behaviour_logp=z, bootstrap_obs=boot)

    def ref_loss(p):
        tokens = jnp.concatenate([obs, boot[:, None]], 1)
        logits, _ = reference_forward(p, tokens, cfg)
        lp = jax.nn.log_softmax(logits[:, :T])
        return -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))

    with jax.default_matmul_precision("highest"):
        want_l, want_g = jax.value_and_grad(ref_loss)(params)
        got_l, got_g = jax.jit(jax.value_and_grad(
            lambda p: agent.loss(p, traj)[0]))(params)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-5)
    leaves = jax.tree_util.tree_leaves_with_path(want_g)
    for (path, w), g in zip(leaves, jax.tree.leaves(got_g)):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, atol=1e-3 * scale + 1e-9, rtol=1e-3,
                                   err_msg=jax.tree_util.keystr(path))


# --------------------------------------------------------------------- SSD


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("T,chunk", [(17, 256), (21, 8)])
def test_ssd_vjp_matches_the_recurrence(impl, T, chunk):
    """Lengths that are not a whole number of chunks (17 tokens in one
    padded chunk, the learner's; 21 in three chunks of 8, whose backward
    recomputes the state entering each chunk).  The Pallas kernel runs in
    interpret mode."""
    B, H, P, N = 2, 3, 4, 5
    k = jax.random.split(jax.random.key(T), 6)
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)))
    A = -jax.random.uniform(k[2], (H,), minval=0.5, maxval=2.0)
    Bm = jax.random.normal(k[3], (B, T, N))
    Cm = jax.random.normal(k[4], (B, T, N))
    w = jax.random.normal(k[5], (B, T, H, P))

    def objective(scan):
        def f(x, dt, A, Bm, Cm):
            y, S = scan(x, dt, A, Bm, Cm)
            return jnp.sum(y * w) + jnp.sum(S)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4))

    prog = lambda *a: ssd_ops.ssd_scan(*a, chunk=chunk, impl=impl,
                                       interpret=impl == "pallas")
    with jax.default_matmul_precision("highest"):
        want_v, want_g = objective(_ssd_recurrence)(x, dt, A, Bm, Cm)
        got_v, got_g = objective(prog)(x, dt, A, Bm, Cm)
    assert float(got_v) == pytest.approx(float(want_v), rel=1e-5)
    for g, wg in zip(got_g, want_g):
        np.testing.assert_allclose(
            g, wg, rtol=RTOL, atol=ATOL * float(jnp.max(jnp.abs(wg))))


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_ssd_vjp_is_finite_under_strong_decay(impl):
    """Decays of up to 16 over steps of up to 8: the chunk's s > t decay
    terms, which the causal mask drops, overflow if exponentiated, and
    their zero cotangent must not turn the gradient into NaN."""
    B, T, H, P, N = 2, 17, 3, 4, 5
    k = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.random.uniform(k[1], (B, T, H), minval=4.0, maxval=8.0)
    A = -jnp.array([1.0, 8.0, 16.0])
    Bm = jax.random.normal(k[2], (B, T, N))
    Cm = jax.random.normal(k[3], (B, T, N))

    def f(*a):
        y, _ = ssd_ops.ssd_scan(*a, chunk=256, impl=impl,
                                interpret=impl == "pallas")
        return jnp.sum(y)

    for g in jax.grad(f, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm):
        assert bool(jnp.all(jnp.isfinite(g)))


# ------------------------------------------------------------------ runner


def _lm_sebulba():
    env = TokenEnv(vocab_size=small_cfg().vocab_size, prompt_len=2,
                   data_vocab=4)
    agent = LMPolicyAgent(small_cfg(), max_seq=env.episode_len)
    return Sebulba(
        optimizer=optim.adam(1e-3),
        config=SebulbaConfig(num_actor_cores=1, threads_per_actor_core=1,
                             actor_batch_size=2,
                             trajectory_length=env.episode_len),
        agent=agent, device_env=env,
    )


def test_runner_stores_no_starting_carry_for_lm_agents():
    """The LM agent declares that its loss never reads the starting carry:
    the actors' ring keeps no snapshot of it, and the drained trajectory
    carries () in its place, while the live carry still threads the act
    steps."""
    seb = _lm_sebulba()
    assert seb.spec.recurrent and not seb.spec.reads_init_carry
    params = seb.agent.init(jax.random.key(0), ())
    device = seb.split.actor_devices[0]
    fleet = seb._fleet
    env_state = jax.device_put(fleet.init(jax.random.key(1)), device)
    obs = jax.device_put(fleet.observe(env_state), device)
    rew_disc = jax.device_put(jnp.zeros((2, 2), jnp.float32), device)
    stats = jax.device_put(fleet.init_stats(), device)
    rng = jax.device_put(jax.random.key(2), device)
    carry = seb._initial_carry(device)
    buf = seb._make_actor_buffer(params, obs, device)
    assert buf.carry0 == ()
    for _ in range(seb.cfg.trajectory_length):
        buf, rng, env_state, obs, rew_disc, carry, stats = seb._device_act_step(
            params, buf, rng, env_state, obs, rew_disc, carry, stats)
    assert int(carry["pos"][0]) == seb.cfg.trajectory_length
    assert set(carry["cache"]["layer_0"]) == {"ssm", "conv"}
    traj, _ = seb._drain(buf, rew_disc, obs)
    assert traj.init_carry == ()


def test_runner_still_stores_the_r2d2_starting_carry():
    from repro.agents.recurrent import RecurrentImpalaAgent, RecurrentMLPActorCritic
    from repro.envs import BatchedHostEnv, HostBandit

    cfg = SebulbaConfig(num_actor_cores=1, threads_per_actor_core=1,
                        actor_batch_size=2, trajectory_length=2)
    net = RecurrentMLPActorCritic(4, hidden=(8,), rnn_width=4)
    seb = Sebulba(
        env_factory=lambda seed: HostBandit(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        network=net, optimizer=optim.adam(1e-3), config=cfg,
        agent=RecurrentImpalaAgent(net, cfg),
    )
    assert seb.spec.reads_init_carry
    params, _ = seb.init(jax.random.key(0), (4,))
    device = seb.split.actor_devices[0]
    obs = jax.device_put(jnp.ones((2, 4), jnp.float32), device)
    buf = seb._make_actor_buffer(params, obs, device)
    assert jax.tree.leaves(buf.carry0)[0].shape == (2, 4)


def test_burn_in_needs_a_stored_carry():
    env = TokenEnv(vocab_size=32, prompt_len=2, data_vocab=4)
    with pytest.raises(ValueError, match="reads_init_carry"):
        Sebulba(
            optimizer=optim.adam(1e-3),
            config=SebulbaConfig(num_actor_cores=1, actor_batch_size=2,
                                 trajectory_length=env.episode_len, burn_in=1),
            agent=LMPolicyAgent(small_cfg(vocab_size=32), max_seq=4),
            device_env=env,
        )


def test_layer_types_are_checked():
    with pytest.raises(ValueError, match="layer_types"):
        small_cfg(layer_types=("mamba", "attention"))
    with pytest.raises(ValueError, match="layer_types"):
        small_cfg(layer_types=("mamba", "moe", "mamba"))
    # published: attention at 5, 15, 25 and 35 of 40
    full = granite.CONFIG
    assert [i for i, k in enumerate(full.layer_types) if k == "attention"] == [5, 15, 25, 35]
    assert (full.d_inner, full.ssm_heads) == (4096, 64)
