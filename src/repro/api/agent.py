"""The unified Podracer agent protocol (`repro.api`).

One canonical signature set serves every agent — feed-forward, recurrent,
on-policy, replay, search-based — so the Podracer cores (Sebulba, Anakin)
contain ZERO runtime arity-sniffing or class-marker checks:

    init(rng, obs_shape)                     -> params
    initial_carry(batch)                     -> carry pytree (() if none)
    act(params, obs, rng, carry)             -> (actions, ActAux, carry)
    loss(params, traj, weights=None)         -> (scalar, LossAux)

``ActAux`` carries the behaviour log-prob plus any agent-specific per-step
``extras`` (e.g. MCTS visit distributions — a dict keyed by
``AgentSpec.extras_keys``, stored in the device trajectory ring).
``LossAux`` carries the learner metrics dict plus per-sequence replay
``priorities`` (``()`` for agents that produce none).  ``weights=None``
means an unweighted loss; replay-capable agents apply PER importance
weights when given.

Capabilities are DECLARED, not sniffed: every agent exposes a frozen
``AgentSpec`` (``agent.spec``) saying whether it is ``recurrent`` (threads
a nonempty carry), ``replay``-capable (accepts importance weights and
returns priorities), and which ``extras_keys`` its act emits.  The spec is
validated once at runner construction by ``resolve_agent`` with fix-it
error messages; nothing about the protocol touches the traced hot path —
NamedTuple auxes flatten to exactly the tuple leaves the pre-protocol code
passed, so the donated act/update jits trace to bit-identical programs.

Migration from the old implicit protocol (3-arg ``act`` for feed-forward
agents, 4-tuple act returns for recurrent ones, ``replay_protocol`` class
markers, bare ``(metrics, td)`` loss aux) is handled by ``resolve_agent``:
an agent with no declared ``spec`` is inspected ONCE here — the signature
sniffing that used to live in ``Sebulba.__init__`` — and wrapped in a
``_LegacyAgent`` adapter presenting the canonical surface.  New agents
should declare a spec and skip the shim (see ARCHITECTURE.md §Protocol).
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Mapping, NamedTuple, Protocol, runtime_checkable

import jax
import numpy as np

PyTree = Any


class ActAux(NamedTuple):
    """Per-step acting outputs besides the actions themselves.

    ``logp`` is the behaviour log-probability of the sampled action under
    the acting policy (what V-trace/PPO correct against); ``extras`` is an
    agent-specific fixed-shape pytree stored per step in the trajectory
    ring — a dict keyed by ``AgentSpec.extras_keys``, or ``()``.
    """

    logp: jax.Array
    extras: Any = ()


class LossAux(NamedTuple):
    """Loss auxiliaries: learner ``metrics`` (a flat dict of scalars,
    folded into the device-resident accumulator) and per-sequence replay
    ``priorities`` (the PER write-back signal; ``()`` when the agent
    declares ``replay=False``)."""

    metrics: Any
    priorities: Any = ()


@dataclasses.dataclass(frozen=True)
class AgentSpec:
    """Declared agent capabilities, validated once at runner construction.

    ``recurrent``  — ``initial_carry(batch)`` returns a nonempty all-zeros
                     carry that ``act`` threads (and Sebulba stores as the
                     R2D2 stored state / resets on episode boundaries).
    ``replay``     — ``loss`` applies importance ``weights`` and returns
                     per-sequence ``LossAux.priorities``; required by
                     Sebulba's replay mode, rejected by the on-policy one.
    ``extras_keys``— exact key set of the dict ``ActAux.extras`` emits
                     (``()`` means no extras).  Gives agent extras (e.g.
                     MuZero visit distributions) a checked, named slot in
                     the trajectory ring instead of an anonymous pytree.
    ``reads_init_carry`` — whether ``loss`` reads ``Trajectory.init_carry``,
                     the carry entering the trajectory's first step.  A
                     recurrent agent that declares False (the LM agents:
                     every trajectory is a whole episode from the zero
                     carry) gets no such snapshot: the runner keeps none
                     in the actors' rings and queues ``()`` in its place.
    """

    recurrent: bool = False
    replay: bool = False
    extras_keys: tuple[str, ...] = ()
    reads_init_carry: bool = True

    def __post_init__(self):
        keys = self.extras_keys
        if isinstance(keys, str):
            keys = (keys,)  # a bare string means ONE key, not its chars
        keys = tuple(keys)
        for k in keys:
            if not isinstance(k, str):
                raise TypeError(
                    f"extras_keys must be strings, got {type(k).__name__}"
                )
        object.__setattr__(self, "extras_keys", keys)


@runtime_checkable
class Agent(Protocol):
    """The canonical Podracer agent. See module docstring for semantics."""

    spec: AgentSpec

    def init(self, rng: jax.Array, obs_shape) -> PyTree: ...

    def initial_carry(self, batch: int) -> PyTree: ...

    def act(
        self, params: PyTree, obs, rng: jax.Array, carry: PyTree = ()
    ) -> tuple[jax.Array, ActAux, PyTree]: ...

    def loss(
        self, params: PyTree, traj, weights: jax.Array | None = None
    ) -> tuple[jax.Array, LossAux]: ...


# --------------------------------------------------------------- validation


_POS_KINDS = (
    inspect.Parameter.POSITIONAL_ONLY,
    inspect.Parameter.POSITIONAL_OR_KEYWORD,
)


def _positional_arity(fn) -> tuple[int, int, bool]:
    """(capable, required, has_var_positional) positional-arg counts of a
    bound method — capable counts defaulted params (what an N-positional
    call can fill), required counts default-less ones."""
    params = inspect.signature(fn).parameters
    capable = sum(p.kind in _POS_KINDS for p in params.values())
    required = sum(
        p.kind in _POS_KINDS and p.default is inspect.Parameter.empty
        for p in params.values()
    )
    var_pos = any(
        p.kind is inspect.Parameter.VAR_POSITIONAL for p in params.values()
    )
    return capable, required, var_pos


def _check_zero_carry(agent, name: str) -> None:
    """Both carry-reset mechanisms (the actor's jnp.where against the
    initial carry, the learner's decay-gate fold) restore ZERO state; a
    nonzero initial carry would silently diverge them.

    The check is on VALUES, not shapes: a carry of any size and structure
    validates so long as every leaf is zero-valued.  An autoregressive
    KV-cache pytree with a position counter (repro/agents/lm_policy.py) is
    the canonical nonzero-shaped, zero-valued carry.
    """
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(
        agent.initial_carry(1)
    )
    for path, leaf in leaves_with_path:
        if np.any(np.asarray(leaf) != 0):
            where = jax.tree_util.keystr(path) or "<root>"
            raise ValueError(
                f"{name}.initial_carry must be all zeros in every leaf, "
                f"but leaf {where} has nonzero entries: episode resets in "
                "the fused actor step and the learner's decay-gate reset "
                "fold (repro/agents/recurrent.py) both restore zero state. "
                "Shape and dtype are unconstrained — a zero-valued KV "
                "cache plus position counter validates fine; only the "
                "t=0 VALUE must be zero"
            )


def validate_agent(agent, spec: AgentSpec) -> None:
    """Check a declared-spec agent against the canonical protocol, raising
    ValueError with a fix-it message on the first violation.  Runs once at
    runner construction — never inside a trace."""
    name = type(agent).__name__
    for method in ("init", "act", "loss", "initial_carry"):
        if not callable(getattr(agent, method, None)):
            raise ValueError(
                f"{name} does not implement the repro.api.Agent protocol: "
                f"missing {method}() — see repro/api/agent.py for the "
                "canonical signatures"
            )
    act_pos = [
        p for p in inspect.signature(agent.act).parameters.values()
        if p.kind in _POS_KINDS
    ]
    var_pos = any(
        p.kind is inspect.Parameter.VAR_POSITIONAL
        for p in inspect.signature(agent.act).parameters.values()
    )
    if not var_pos and len(act_pos) < 4:
        raise ValueError(
            f"{name}.act takes {len(act_pos)} positional arguments; the "
            "canonical protocol is act(params, obs, rng, carry) -> "
            "(actions, ActAux(logp, extras), carry) — feed-forward agents "
            "receive (and should return) the empty () carry"
        )
    if not var_pos and act_pos[3].name != "carry":
        # the runner passes the carry positionally in slot 4 on EVERY act;
        # a knob parked there (e.g. temperature=1.0) would silently
        # receive () inside the jit trace — fail at construction instead
        raise ValueError(
            f"{name}.act's 4th positional parameter is "
            f"{act_pos[3].name!r}, but the canonical protocol passes the "
            "carry there (act(params, obs, rng, carry)); rename it, and "
            "make extra knobs keyword-only (e.g. `*, "
            f"{act_pos[3].name}=...`)"
        )
    capable, _required, var_pos = _positional_arity(agent.loss)
    if not var_pos and capable < 3:
        raise ValueError(
            f"{name}.loss takes {capable} positional arguments; the "
            "canonical protocol is loss(params, trajectory, weights=None) "
            "-> (scalar, LossAux(metrics, priorities)) — weights=None "
            "means unweighted"
        )
    if spec.recurrent:
        if not jax.tree.leaves(agent.initial_carry(1)):
            raise ValueError(
                f"{name} declares AgentSpec(recurrent=True) but "
                "initial_carry(batch) returns an empty pytree; recurrent "
                "agents must expose the zero carry the runner threads, "
                "stores, and resets"
            )
        _check_zero_carry(agent, name)
    elif jax.tree.leaves(agent.initial_carry(1)):
        raise ValueError(
            f"{name}.initial_carry returns a nonempty carry but the "
            "declared AgentSpec has recurrent=False; declare "
            "AgentSpec(recurrent=True) so the runner threads (and stores) "
            "the carry"
        )


def validate_extras(extras_spec, spec: AgentSpec, name: str) -> None:
    """Check act's abstract ``extras`` structure against the declared
    ``extras_keys`` (called by runners after ``jax.eval_shape`` of act, so
    it costs nothing on the hot path)."""
    if spec.extras_keys:
        if not isinstance(extras_spec, Mapping):
            raise ValueError(
                f"{name} declares AgentSpec.extras_keys="
                f"{spec.extras_keys} so act must emit its extras as a "
                f"dict with exactly those keys; got "
                f"{type(extras_spec).__name__}"
            )
        got = tuple(sorted(extras_spec))
        if got != tuple(sorted(spec.extras_keys)):
            raise ValueError(
                f"{name}.act extras keys {got} do not match the declared "
                f"AgentSpec.extras_keys {tuple(sorted(spec.extras_keys))}"
            )
    elif jax.tree.leaves(extras_spec):
        raise ValueError(
            f"{name}.act emits extras but declares no "
            "AgentSpec.extras_keys; name them (a dict of fixed-shape "
            "arrays) so their trajectory-ring storage is part of the "
            "agent's declared surface"
        )


# --------------------------------------------------- legacy-protocol shim


class _LegacyAgent:
    """Adapter presenting the canonical protocol over a pre-``repro.api``
    agent (3-arg feed-forward ``act``, 4-tuple recurrent act returns,
    ``replay_protocol`` class marker, bare loss aux).  Built only by
    ``resolve_agent`` for agents with no declared spec — new agents should
    declare an ``AgentSpec`` instead and skip this shim entirely."""

    def __init__(self, agent, spec: AgentSpec):
        self.wrapped = agent
        self.spec = spec

    def init(self, rng, obs_shape):
        return self.wrapped.init(rng, obs_shape)

    def initial_carry(self, batch: int):
        if self.spec.recurrent:
            return self.wrapped.initial_carry(batch)
        return ()

    def act(self, params, obs, rng, carry=()):
        if self.spec.recurrent:
            actions, logp, extras, carry = self.wrapped.act(
                params, obs, rng, carry
            )
            return actions, ActAux(logp, extras), carry
        actions, logp, extras = self.wrapped.act(params, obs, rng)
        return actions, ActAux(logp, extras), ()

    def loss(self, params, traj, weights=None):
        if self.spec.replay:
            total, (metrics, priorities) = self.wrapped.loss(
                params, traj, weights
            )
            return total, LossAux(metrics, priorities)
        total, metrics = self.wrapped.loss(params, traj)
        return total, LossAux(metrics)


def _derive_legacy_spec(agent, replay_hint: bool) -> AgentSpec:
    """Inspect a spec-less agent ONCE (the sniffing that used to live in
    ``Sebulba.__init__``, now quarantined to the migration shim), raising
    the same actionable errors on malformed agents.

    ``replay_hint`` disambiguates the one capability the old implicit
    protocol could not express: a marker-less agent whose loss takes three
    positional arguments is replay-capable *iff the runner is in replay
    mode* (the pre-protocol replay learner accepted any 3-positional loss
    and assumed the ``(metrics, td)`` aux; the same signature on-policy
    meant a plain metrics aux).  Declared-spec agents never need the hint.
    """
    name = type(agent).__name__
    recurrent = callable(getattr(agent, "initial_carry", None))
    capable, required, var_pos = _positional_arity(agent.act)
    if recurrent and not var_pos and capable < 4:
        raise ValueError(
            "recurrent agents (initial_carry present) must accept "
            f"act(params, obs, rng, carry); {name}.act takes {capable} "
            "positional arguments"
        )
    if not recurrent and required > 3:
        raise ValueError(
            f"{name}.act requires {required} positional arguments but the "
            "agent has no initial_carry; recurrent agents must expose "
            "initial_carry(batch_size) so the runner knows to thread "
            "(and store) a carry"
        )
    if recurrent:
        _check_zero_carry(agent, name)
    loss_capable, _req, loss_var_pos = _positional_arity(agent.loss)
    loss_weighted = loss_var_pos or loss_capable >= 3
    replay = bool(getattr(agent, "replay_protocol", False))
    if replay and not loss_weighted:
        # the replay learner calls loss positionally with three arguments
        raise ValueError(
            "replay-protocol agents need loss(params, trajectory, "
            "importance_weights) callable with three positional "
            f"arguments; {name}.loss accepts {loss_capable}"
        )
    if replay_hint and loss_weighted:
        replay = True
    return AgentSpec(recurrent=recurrent, replay=replay)


def resolve_agent(agent, *, replay_hint: bool = False) -> tuple[Agent, AgentSpec]:
    """Resolve any agent to ``(canonical agent, validated AgentSpec)``.

    Declared-spec agents are validated (signature conformance, zero-carry
    invariant) and returned as-is — zero indirection on the hot path.
    Spec-less agents go through the legacy derivation + adapter
    (``replay_hint`` — whether the calling runner is in replay mode —
    feeds only that derivation; see ``_derive_legacy_spec``).  All errors
    carry fix-it messages and fire here, at construction — never in a jit
    trace on the first actor step.
    """
    spec = getattr(agent, "spec", None)
    if isinstance(spec, AgentSpec):
        validate_agent(agent, spec)
        return agent, spec
    spec = _derive_legacy_spec(agent, replay_hint)
    return _LegacyAgent(agent, spec), spec


def is_legacy_adapter(agent) -> bool:
    """True for agents wrapped by the migration shim (their derived spec
    cannot declare extras_keys, so extras checks don't apply to them)."""
    return isinstance(agent, _LegacyAgent)
