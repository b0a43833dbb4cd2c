"""ISSUE 5 acceptance: the unified repro.api agent/runner protocol.

  * every registered agent passes ``AgentSpec`` validation and resolves
    WITHOUT the legacy adapter (the zoo is fully migrated);
  * the act/initial_carry shape-and-dtype contract holds: actions (B,),
    float logp (B,), extras keyed exactly by ``AgentSpec.extras_keys``,
    carry out mirroring carry in;
  * ``loss(weights=None)`` equals explicit-ones weights for replay agents
    (the canonical "None means unweighted" pin) and on-policy agents
    reject weights with a fix-it error;
  * ``core/sebulba.py`` contains no runtime arity sniffing or class-marker
    checks — all agent validation goes through ``repro.api``;
  * the ``run()``/``fit()`` result schema is one dict across on-policy
    Sebulba, off-policy Sebulba, and Anakin (absent counters 0, never
    missing);
  * runner-owned checkpointing: ``checkpoint_every`` writes
    ``param_version``-stamped files and ``restore_from`` round-trips.
"""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, optim
from repro.envs import BatchedHostEnv, Catch, HostBandit

B, T = 4, 6

# LM fixtures trace a real (if toy) transformer through act and loss —
# jit-heavy enough to stay out of the fast tier with the rest of the LM
# surface (ISSUE 9 satellite), without losing conformance coverage.
_PARAMS = [
    pytest.param(n, marks=pytest.mark.slow) if n.startswith("lm_") else n
    for n in api.registered_agents()
]


@pytest.fixture(scope="module", params=_PARAMS)
def fixture(request):
    return request.param, api.make_agent(request.param)


def _obs_dtype(fx):
    return jnp.float32 if fx.obs_dtype is None else fx.obs_dtype


def _random_obs(rng, shape, dtype, num_actions):
    """np.RandomState -> obs array; integer dtypes mean token observations
    bounded by the vocabulary (= num_actions for LM agents)."""
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.randint(0, num_actions, shape), dtype)
    return jnp.asarray(rng.rand(*shape), dtype)


def _act(agent, obs_shape, batch=B, seed=0, obs_dtype=jnp.float32,
         num_actions=4):
    params = agent.init(jax.random.key(seed), obs_shape)
    carry = agent.initial_carry(batch)
    obs = _random_obs(
        np.random.RandomState(seed + 1), (batch,) + obs_shape, obs_dtype,
        num_actions,
    )
    actions, aux, new_carry = jax.jit(agent.act)(
        params, obs, jax.random.key(seed + 2), carry
    )
    return params, carry, actions, aux, new_carry


def _make_traj(agent, spec, params, obs_shape, num_actions, seed=0,
               obs_dtype=jnp.float32):
    """Synthetic trajectory matching the agent's declared surface, shaped
    exactly as the actor ring would drain it (extras from act's abstract
    output, init_carry from initial_carry)."""
    from repro.data.trajectory import Trajectory

    rng = np.random.RandomState(seed)
    carry_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        agent.initial_carry(B),
    )
    obs_spec = jax.ShapeDtypeStruct((B,) + obs_shape, obs_dtype)
    _, aux_spec, _ = jax.eval_shape(
        agent.act, params, obs_spec, jax.random.key(0), carry_spec
    )
    extras = jax.tree.map(
        lambda s: jnp.asarray(
            rng.rand(s.shape[0], T, *s.shape[1:]), s.dtype
        ),
        aux_spec.extras,
    )
    init_carry = jax.tree.map(
        lambda s: jnp.asarray(rng.rand(*s.shape), s.dtype), carry_spec
    )
    return Trajectory(
        obs=_random_obs(rng, (B, T) + obs_shape, obs_dtype, num_actions),
        actions=jnp.asarray(rng.randint(0, num_actions, (B, T)), jnp.int32),
        rewards=jnp.asarray(rng.rand(B, T), jnp.float32),
        discounts=jnp.full((B, T), 0.99, jnp.float32),
        behaviour_logp=jnp.asarray(
            np.log(rng.uniform(0.2, 0.9, (B, T))), jnp.float32
        ),
        bootstrap_obs=_random_obs(rng, (B,) + obs_shape, obs_dtype,
                                  num_actions),
        extras=extras,
        init_carry=init_carry,
    )


# ------------------------------------------------------- spec conformance


def test_registry_covers_the_zoo():
    names = api.registered_agents()
    for expected in ("impala", "actor_critic", "ppo", "muzero",
                     "replay_impala", "recurrent_impala",
                     "recurrent_replay_impala", "lm_policy",
                     "lm_replay_policy"):
        assert expected in names


def test_agent_resolves_without_legacy_adapter(fixture):
    name, fx = fixture
    assert isinstance(fx.agent.spec, api.AgentSpec), name
    resolved, spec = api.resolve_agent(fx.agent)
    assert resolved is fx.agent, (
        f"{name} should resolve natively, not through the migration shim"
    )
    assert spec is fx.agent.spec
    assert not api.is_legacy_adapter(resolved)


def test_act_contract_shapes_and_dtypes(fixture):
    name, fx = fixture
    spec = fx.agent.spec
    params, carry, actions, aux, new_carry = _act(
        fx.agent, fx.obs_shape, obs_dtype=_obs_dtype(fx),
        num_actions=fx.num_actions,
    )
    assert actions.shape == (B,), name
    assert jnp.issubdtype(actions.dtype, jnp.integer), name
    assert isinstance(aux, api.ActAux), name
    assert aux.logp.shape == (B,), name
    assert jnp.issubdtype(aux.logp.dtype, jnp.floating), name
    # extras keyed exactly by the declaration
    if spec.extras_keys:
        assert sorted(aux.extras) == sorted(spec.extras_keys), name
        for leaf in jax.tree.leaves(aux.extras):
            assert leaf.shape[0] == B, name
    else:
        assert jax.tree.leaves(aux.extras) == [], name
    # carry out mirrors carry in (structure, shapes, dtypes)
    assert jax.tree.structure(new_carry) == jax.tree.structure(carry), name
    for a, b in zip(jax.tree.leaves(new_carry), jax.tree.leaves(carry)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
    # recurrent declaration <-> a real carry
    assert spec.recurrent == bool(jax.tree.leaves(carry)), name


def test_loss_contract_and_weights_pin(fixture):
    name, fx = fixture
    agent, spec = fx.agent, fx.agent.spec
    params = agent.init(jax.random.key(0), fx.obs_shape)
    traj = _make_traj(agent, spec, params, fx.obs_shape, fx.num_actions,
                      obs_dtype=_obs_dtype(fx))
    total, aux = jax.jit(agent.loss)(params, traj)
    assert total.shape == () and np.isfinite(float(total)), name
    assert isinstance(aux, api.LossAux), name
    assert aux.metrics and all(
        np.isfinite(float(v)) for v in jax.tree.leaves(aux.metrics)
    ), name
    if spec.replay:
        assert np.asarray(aux.priorities).shape == (B,), name
        # the canonical pin: weights=None IS the unweighted loss
        total_ones, aux_ones = jax.jit(agent.loss)(
            params, traj, jnp.ones((B,), jnp.float32)
        )
        np.testing.assert_allclose(
            float(total), float(total_ones), rtol=1e-6,
            err_msg=f"{name}: loss(weights=None) != loss(ones)",
        )
        np.testing.assert_allclose(
            np.asarray(aux.priorities), np.asarray(aux_ones.priorities),
            rtol=1e-6,
        )
    else:
        assert aux.priorities == (), name
        with pytest.raises(ValueError, match="importance weights"):
            agent.loss(params, traj, jnp.ones((B,), jnp.float32))


# --------------------------------------------- validation fix-it messages


def test_extras_declaration_mismatch_rejected():
    spec = jax.ShapeDtypeStruct((B, 3), jnp.float32)
    with pytest.raises(ValueError, match="do not match the declared"):
        api.validate_extras(
            {"bar": spec}, api.AgentSpec(extras_keys=("foo",)), "X"
        )
    with pytest.raises(ValueError, match="extras as a dict"):
        api.validate_extras(spec, api.AgentSpec(extras_keys=("foo",)), "X")
    with pytest.raises(ValueError, match="declares no"):
        api.validate_extras({"bar": spec}, api.AgentSpec(), "X")
    api.validate_extras({"foo": spec}, api.AgentSpec(extras_keys=("foo",)),
                        "X")  # exact match passes
    api.validate_extras((), api.AgentSpec(), "X")


def test_declared_spec_signature_validation_fix_it():
    class MissingCarryArg:
        spec = api.AgentSpec(recurrent=True)

        def init(self, rng, obs_shape):
            return {}

        def initial_carry(self, batch):
            return jnp.zeros((batch, 2))

        def act(self, params, obs, rng):  # lost the carry
            raise NotImplementedError

        def loss(self, params, traj, weights=None):
            raise NotImplementedError

    with pytest.raises(ValueError, match=r"act\(params, obs, rng, carry\)"):
        api.resolve_agent(MissingCarryArg())

    class NoWeightsParam(MissingCarryArg):
        def act(self, params, obs, rng, carry):
            raise NotImplementedError

        def loss(self, params, traj):  # lost the weights
            raise NotImplementedError

    with pytest.raises(ValueError, match=r"weights=None"):
        api.resolve_agent(NoWeightsParam())

    class UndeclaredCarry(NoWeightsParam):
        spec = api.AgentSpec(recurrent=False)  # lies about the carry

        def loss(self, params, traj, weights=None):
            raise NotImplementedError

    with pytest.raises(ValueError, match="recurrent=True"):
        api.resolve_agent(UndeclaredCarry())


class _KVCarryAgent:
    """Minimal declared-spec agent with an LM-shaped carry: a zero-valued
    but decidedly nonzero-SHAPED KV-cache pytree plus position counter."""

    spec = api.AgentSpec(recurrent=True)

    def __init__(self, pos_offset=0):
        self._off = pos_offset

    def init(self, rng, obs_shape):
        return {}

    def initial_carry(self, batch):
        return {
            "cache": {
                "layer_0": {
                    "k": jnp.zeros((batch, 8, 2, 4), jnp.bfloat16),
                    "v": jnp.zeros((batch, 8, 2, 4), jnp.bfloat16),
                }
            },
            "pos": jnp.full((batch,), self._off, jnp.int32),
        }

    def act(self, params, obs, rng, carry):
        raise NotImplementedError

    def loss(self, params, traj, weights=None):
        raise NotImplementedError


def test_zero_valued_kv_cache_carry_validates():
    """ISSUE 9 satellite: the zero-carry check is on VALUES, not shapes —
    a KV-cache carry with a position counter must resolve natively."""
    resolved, spec = api.resolve_agent(_KVCarryAgent())
    assert spec.recurrent and not api.is_legacy_adapter(resolved)


def test_nonzero_carry_rejected_naming_the_leaf():
    """The fix-it error pinpoints WHICH leaf breaks the zero-value
    invariant (here the position counter) and spells out that shape/dtype
    are unconstrained."""
    with pytest.raises(ValueError, match=r"leaf \['pos'\]"):
        api.resolve_agent(_KVCarryAgent(pos_offset=3))
    with pytest.raises(ValueError, match="must be all zeros"):
        api.resolve_agent(_KVCarryAgent(pos_offset=3))


def test_sebulba_core_has_no_arity_sniffing():
    """Acceptance: no runtime arity-sniffing or class-marker checks remain
    in core/sebulba.py — agent introspection lives in repro.api only."""
    import repro.core.sebulba as mod

    src = pathlib.Path(mod.__file__).read_text()
    assert "import inspect" not in src
    assert "inspect." not in src
    assert "replay_protocol" not in src
    assert "getattr(self.agent" not in src
    assert "resolve_agent" in src  # the one sanctioned entry point


# ------------------------------------------------- unified runner surface


def _tiny_sebulba(replay=None):
    from repro.agents import BatchedMLPActorCritic
    from repro.core.sebulba import Sebulba, SebulbaConfig

    return Sebulba(
        env_factory=lambda seed: HostBandit(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        network=BatchedMLPActorCritic(4, hidden=(16,)),
        optimizer=optim.adam(1e-3),
        config=SebulbaConfig(
            num_actor_cores=1, threads_per_actor_core=1,
            actor_batch_size=4, trajectory_length=2, replay=replay,
        ),
    )


def _tiny_anakin():
    from repro.agents.actor_critic import MLPActorCritic
    from repro.core.anakin import Anakin, AnakinConfig

    env = Catch()
    return Anakin(
        env, MLPActorCritic(env.num_actions, (16,)), optim.sgd(1e-2),
        AnakinConfig(unroll_length=5, batch_per_device=8,
                     iterations_per_call=2),
    )


def test_runners_satisfy_the_protocol():
    assert isinstance(_tiny_sebulba(), api.Runner)
    assert isinstance(_tiny_anakin(), api.Runner)


def test_result_schema_unified_across_all_paths():
    """Satellite: one documented result schema.  Counters an architecture
    does not have read 0, never missing."""
    from repro.configs.base import ReplayConfig

    out_on = _tiny_sebulba().fit(jax.random.key(0), total_frames=64)
    out_off = _tiny_sebulba(
        ReplayConfig(capacity=16, sample_batch_size=4, min_size=4)
    ).fit(jax.random.key(0), total_frames=160)
    out_ank = _tiny_anakin().fit(jax.random.key(0), total_frames=80)

    for name, out in (("on", out_on), ("off", out_off), ("anakin", out_ank)):
        missing = set(api.RESULT_KEYS) - set(out)
        assert not missing, f"{name} result missing {missing}"
        for key in ("updates", "frames", "param_version", "publishes_sent",
                    "publishes_skipped", "publishes_aliased", "put_blocked",
                    "traj_dropped", "replay_size", "checkpoints_saved",
                    "policy_lag_max"):
            assert isinstance(out[key], int), (name, key, type(out[key]))
        assert isinstance(out["policy_lag_mean"], float), name
    # architecture-absent counters are zeros, not gaps
    assert out_on["replay_size"] == 0
    assert out_off["replay_size"] > 0
    for key in ("publishes_sent", "publishes_skipped", "publishes_aliased",
                "put_blocked", "traj_dropped", "replay_size",
                "policy_lag_mean", "policy_lag_max"):
        assert out_ank[key] == 0
    assert out_ank["param_version"] == out_ank["updates"]


# --------------------------------------------------- runner checkpointing


def test_sebulba_checkpoint_wiring(tmp_path):
    """Satellite: the runner owns persistence — boundary saves stamped
    with param_version, a final save, and restore_from warm-starting."""
    d = str(tmp_path / "ckpts")
    seb = _tiny_sebulba()
    out = seb.fit(
        jax.random.key(0), total_frames=64, checkpoint_dir=d,
        checkpoint_every=2,
    )
    assert out["checkpoints_saved"] >= 1
    latest = api.latest_checkpoint(d)
    assert latest is not None
    restored, meta = api.restore_checkpoint(latest, out["params"])
    assert meta["param_version"] == out["param_version"]
    assert meta["updates"] == out["updates"]
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(out["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # warm start from the directory (latest stamp wins)
    seb2 = _tiny_sebulba()
    out2 = seb2.fit(jax.random.key(1), total_frames=32, restore_from=d)
    assert out2["updates"] > 0


def test_checkpoint_every_requires_dir():
    with pytest.raises(ValueError, match="checkpoint_dir"):
        api.CheckpointPolicy(None, 5)


def test_restore_continues_the_version_line(tmp_path):
    """Resuming into the SAME checkpoint_dir must stamp new checkpoints
    ABOVE the restored one — otherwise latest_checkpoint keeps resolving
    to the stale pre-restore params."""
    d = str(tmp_path / "ck")
    out1 = _tiny_sebulba().fit(
        jax.random.key(0), total_frames=64, checkpoint_dir=d,
        checkpoint_every=2,
    )
    first_latest = api.latest_checkpoint(d)
    out2 = _tiny_sebulba().fit(
        jax.random.key(1), total_frames=64, checkpoint_dir=d,
        checkpoint_every=2, restore_from=d,
    )
    assert out2["param_version"] > out1["param_version"]
    latest = api.latest_checkpoint(d)
    assert latest != first_latest
    _, meta = api.restore_checkpoint(latest, out2["params"])
    assert meta["param_version"] == out2["param_version"]
    assert meta["updates"] > out1["updates"]  # cumulative stamps

    # same continuity on Anakin's block-granular fit
    d2 = str(tmp_path / "ck_ank")
    a1 = _tiny_anakin().fit(jax.random.key(0), total_frames=80,
                            checkpoint_dir=d2, checkpoint_every=2)
    a2 = _tiny_anakin().fit(jax.random.key(1), total_frames=80,
                            checkpoint_dir=d2, checkpoint_every=2,
                            restore_from=d2)
    assert a2["param_version"] == a1["param_version"] + a2["updates"]
    _, meta2 = api.restore_checkpoint(d2, a2["params"])
    assert meta2["param_version"] == a2["param_version"]


def test_restore_does_not_resave_the_restored_boundary():
    """A resumed fit's first boundary save must land at the NEXT every-N
    boundary, not immediately duplicate the just-restored params."""
    policy = api.CheckpointPolicy("unused-dir", 100, base_updates=250)
    fired = []
    policy._save = lambda params, **kw: fired.append(kw["updates"])
    policy.maybe_save(None, param_version=251, updates=251, frames=0)
    policy.maybe_save(None, param_version=299, updates=299, frames=0)
    assert fired == []  # still inside the restored boundary
    policy.maybe_save(None, param_version=300, updates=300, frames=0)
    assert fired == [300]
    # a resumed fit that trained NOTHING must not re-write the restored
    # params from final_save (updates is cumulative == the base)
    idle = api.CheckpointPolicy("unused-dir", 100, base_updates=250)
    idle._save = lambda params, **kw: fired.append(("final", kw["updates"]))
    idle.final_save(None, param_version=251, updates=250, frames=0)
    assert fired == [300]
    idle.final_save(None, param_version=252, updates=251, frames=0)
    assert fired == [300, ("final", 251)]


def test_latest_checkpoint_survives_nine_digit_versions(tmp_path):
    """Stamps outgrow the 8-digit zero padding without disappearing from
    restore (numeric compare, not lexical; \\d+ not \\d{8})."""
    d = str(tmp_path)
    for version in (99_999_999, 100_000_000):
        api.save_checkpoint(d, {"w": jnp.zeros((2,))}, param_version=version)
    assert api.latest_checkpoint(d) == api.checkpoint_path(d, 100_000_000)
    _, meta = api.restore_checkpoint(d, {"w": jnp.zeros((2,))})
    assert meta["param_version"] == 100_000_000


def test_agentspec_extras_keys_string_footgun():
    """A bare string must mean one key, not its characters."""
    assert api.AgentSpec(extras_keys="visit_probs").extras_keys == (
        "visit_probs",
    )
    with pytest.raises(TypeError, match="strings"):
        api.AgentSpec(extras_keys=(1,))


def test_legacy_markerless_replay_agent_still_accepted():
    """Pre-protocol behavior pin: in replay mode, a spec-less agent whose
    loss takes three positional args (no replay_protocol marker) was
    accepted with the (metrics, td) aux convention — the legacy shim must
    keep accepting it (the replay hint disambiguates what a bare 3-arg
    loss means)."""
    from repro.agents import BatchedMLPActorCritic
    from repro.configs.base import ReplayConfig
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.rl import losses as L

    class MarkerlessReplay:
        def __init__(self, network):
            self.net = network

        def init(self, rng, obs_shape):
            return self.net.init(rng, obs_shape)

        def act(self, params, obs, rng):  # legacy 3-arg, 3-tuple
            logits, _ = self.net.apply(params, obs)
            actions = jax.random.categorical(rng, logits)
            return actions, L.log_prob(logits, actions), ()

        def loss(self, params, traj, weights=None):  # legacy (metrics, td)
            B, T = traj.actions.shape
            obs_flat = traj.obs.reshape((B * T,) + traj.obs.shape[2:])
            logits, values = self.net.apply(params, obs_flat)
            out = L.weighted_impala_loss(
                logits.reshape(B, T, -1), values.reshape(B, T),
                traj.actions, traj.behaviour_logp, traj.rewards,
                traj.discounts,
                self.net.apply(params, traj.bootstrap_obs)[1],
                importance_weights=weights,
            )
            return out.total, ({"loss": out.total}, out.per_seq_td)

    net = BatchedMLPActorCritic(4, hidden=(16,))
    seb = Sebulba(
        env_factory=lambda seed: HostBandit(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        network=net, optimizer=optim.adam(1e-3),
        config=SebulbaConfig(
            num_actor_cores=1, threads_per_actor_core=1,
            actor_batch_size=4, trajectory_length=2,
            replay=ReplayConfig(capacity=16, sample_batch_size=4,
                                min_size=4),
        ),
        agent=MarkerlessReplay(net),
    )
    assert seb.spec.replay and api.is_legacy_adapter(seb.agent)
    out = seb.fit(jax.random.key(0), total_frames=160)
    assert out["updates"] > 0 and np.isfinite(out["metrics"]["loss"])
    # ...while the SAME signature on-policy still means an unweighted
    # legacy agent (plain metrics aux) and must not be marked replay
    _, spec_on = api.resolve_agent(MarkerlessReplay(net), replay_hint=False)
    assert not spec_on.replay


def test_anakin_checkpoint_block_granularity(tmp_path):
    """checkpoint_every smaller than the compiled block still saves once
    per crossed boundary (updates advance iterations_per_call at a time)."""
    d = str(tmp_path / "ck")
    out = _tiny_anakin().fit(
        jax.random.key(0), total_frames=240, checkpoint_dir=d,
        checkpoint_every=1,
    )
    assert out["checkpoints_saved"] >= 2
    _, meta = api.restore_checkpoint(d, out["params"])
    assert meta["param_version"] == out["param_version"]
