"""Replay sharded across the Sebulba learner mesh.

Each learner core owns an independent ``capacity / L`` slice of the ring
(paper Fig. 3 dataflow, extended off-policy): actor trajectory shards are
already laid out batch-over-learners by ``Sebulba._shard_for_learners``, so
an insert is a purely local write on every core — no collective, no
host round-trip.  Sampling likewise draws ``batch / L`` slots per core and
the results compose into one globally-sharded batch, exactly the layout the
learner's ``shard_map`` update consumes.

The scalar cursors (``insert_pos``, ``total_added``) are *replicated*: every
core inserts the same number of items per call, so the local cursors stay
bit-identical across shards and can be read host-side without a gather.

Sampling RNG: the caller passes one key; each shard folds in its mesh axis
index, so shards draw decorrelated slots while the whole operation stays a
pure deterministic function of (state, key).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.replay import buffer
from repro.replay.buffer import ReplayState

PyTree = Any


def renormalize_probs(local_probs, allocation: int, batch_size: int):
    """One shard's local selection probabilities -> global.

    A draw fanned across shards selects item i with
    ``P(shard) * P(i | shard)``; when ``allocation`` of the
    ``batch_size`` draws go to this shard, ``P(shard)`` is the
    allocation fraction.  Shared by the in-host sharded ring and the
    cross-host routing layer (repro/distributed/routing.py) so the PER
    correction sees ONE coherent distribution over whatever shard set
    currently survives.
    """
    return local_probs * (allocation / batch_size)


def global_importance_weights(probs, global_size: int, beta: float):
    """PER bias correction against the GLOBAL buffer: ``(N * P(i))^-beta``
    normalized by the batch max.  ``global_size`` is the valid-slot
    count summed over every surviving shard — after a shard is lost,
    callers re-normalize over what remains rather than training on the
    stale pre-loss N."""
    w = (max(global_size, 1) * np.asarray(probs, np.float64)) ** (-beta)
    return (w / max(float(np.max(w)), 1e-20)).astype(np.float32)


class ShardedReplay:
    """Host-side handle for a replay ring sharded over a 1-D device mesh."""

    def __init__(
        self,
        mesh: Mesh,
        capacity: int,
        *,
        prioritized: bool = False,
        priority_exponent: float = 0.6,
        axis_name: str = "batch",
    ):
        self.mesh = mesh
        self.axis = axis_name
        self.num_shards = mesh.shape[axis_name]
        if capacity % self.num_shards != 0:
            raise ValueError(
                f"capacity {capacity} must divide across {self.num_shards} "
                "learner shards"
            )
        self.capacity = capacity
        self.prioritized = prioritized
        self.priority_exponent = priority_exponent
        self._insert_fn = None
        self._update_fn = None
        self._sample_fns: dict[int, Any] = {}

    # ------------------------------------------------------------- specs

    def state_spec(self, tree: PyTree) -> ReplayState:
        """PartitionSpec tree: ring dims over the mesh, cursors replicated."""
        return ReplayState(
            storage=jax.tree.map(lambda _: P(self.axis), tree),
            priorities=P(self.axis),
            insert_pos=P(),
            total_added=P(),
        )

    def batch_spec(self, tree: PyTree) -> PyTree:
        return jax.tree.map(lambda _: P(self.axis), tree)

    # ------------------------------------------------------------- setup

    def init(self, example: PyTree) -> ReplayState:
        """Allocate the sharded ring from a (global-batch) example pytree."""
        spec = self.state_spec(example)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            spec,
            is_leaf=lambda x: isinstance(x, P),
        )
        fn = jax.jit(
            lambda ex: buffer.init(ex, self.capacity), out_shardings=shardings
        )
        state = fn(example)
        self._build(state, example)
        return state

    def _build(self, state: ReplayState, example: PyTree) -> None:
        spec = self.state_spec(example)
        bspec = self.batch_spec(example)
        # a re-init with a different trajectory structure must not reuse
        # sample fns compiled against the previous spec
        self._sample_fns.clear()

        def _insert(st, batch):
            # global-max default priorities: see buffer.insert's axis_name note
            return buffer.insert(
                st, batch,
                axis_name=self.axis if self.prioritized else None,
            )

        self._insert_fn = jax.jit(
            jax.shard_map(
                _insert, mesh=self.mesh, in_specs=(spec, bspec),
                out_specs=spec,
                check_vma=False,
            ),
            donate_argnums=0,
        )

        def _update(st, idx, new_p):
            return buffer.update_priorities(st, idx, new_p)

        self._update_fn = jax.jit(
            jax.shard_map(
                _update, mesh=self.mesh,
                in_specs=(spec, P(self.axis), P(self.axis)),
                out_specs=spec,
                check_vma=False,
            ),
            donate_argnums=0,
        )
        self._spec = spec
        self._bspec = bspec

    # --------------------------------------------------------------- ops

    def _require_built(self) -> None:
        if self._insert_fn is None:
            raise RuntimeError(
                "ShardedReplay ops need the compiled sharded paths: call "
                "init(example) first (it allocates the ring and builds them)"
            )

    def insert(self, state: ReplayState, batch: PyTree) -> ReplayState:
        """Insert a globally-sharded batch; every shard writes locally."""
        self._require_built()
        return self._insert_fn(state, batch)

    def sample(self, state: ReplayState, rng: jax.Array, batch_size: int):
        """Draw a globally-sharded batch of ``batch_size`` slots.

        Returns (batch, idx, probs); ``idx`` are *shard-local* slot indices,
        valid only for ``update_priorities`` on this same sharded state.
        """
        if batch_size % self.num_shards != 0:
            raise ValueError(
                f"sample batch {batch_size} must divide across "
                f"{self.num_shards} shards"
            )
        self._require_built()
        fn = self._sample_fns.get(batch_size)
        if fn is None:
            local = batch_size // self.num_shards

            def _sample(st, key):
                key = jax.random.fold_in(key, jax.lax.axis_index(self.axis))
                return buffer.sample(
                    st, key, local,
                    prioritized=self.prioritized,
                    priority_exponent=self.priority_exponent,
                )

            fn = jax.jit(
                jax.shard_map(
                    _sample, mesh=self.mesh,
                    in_specs=(self._spec, P()),
                    out_specs=(self._bspec, P(self.axis), P(self.axis)),
                    check_vma=False,
                )
            )
            self._sample_fns[batch_size] = fn
        return fn(state, rng)

    def update_priorities(self, state, idx, new_priorities) -> ReplayState:
        self._require_built()
        return self._update_fn(state, idx, new_priorities)

    def size(self, state: ReplayState) -> int:
        """Global slot count = shards x the (replicated) local size."""
        local = min(int(state.total_added), self.capacity // self.num_shards)
        return self.num_shards * local
