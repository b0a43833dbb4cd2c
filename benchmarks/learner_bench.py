"""Learner-pipeline benchmark (before/after for the donated Sebulba learner).

Two sections, written into ``BENCH_learner.json`` by
``benchmarks/run.py --suite learner``:

  * ``update`` — latency of one on-policy learner update at the B=4 and
    B=32 operating points, comparing

      - ``legacy`` — the pre-PR learner path, frozen here: the same
        shard_map'd update program jitted once but with NO buffer donation
        (params/opt_state double-buffer every call) and a fresh metrics
        pytree returned to host handles per update;
      - ``fused``  — the current path: compile-cached per trajectory
        shape, opt_state/trajectory/metrics-accumulator donated (params
        too where no actor core is a learner core), metrics folded into
        a device-resident accumulator.

    Compilation is hoisted out of every timed window (both variants are
    warmed up first, and all consumable inputs — fresh trajectories and
    params/opt_state copies — are created between, never inside, the
    timed loops; both variants get identical churn).  B=4 is the
    overhead-dominated operating point where the pipeline glue shows; at
    B=32 the update is conv-grad compute-bound on this CPU container
    (~95% of the 100+ ms step is XLA compute identical in both variants),
    so wall-clock sits at parity there and the structural win is the
    deterministic ``*_alloc_bytes_per_update`` / ``update_in_place``
    fields: donation rewrites the learner state in place instead of
    double-buffering it every update — the accelerator-regime saving
    (HBM allocation + copy) that CPU wall-clock cannot surface.  On one
    device, which both acts and learns, params are not donated: the
    update writes one fresh params set, which the actors' slot then
    takes without a copy.

  * ``publish`` — parameter-publish transfers over a fixed update count,
    publish-every-update (pre-PR, ``publish_throttle=False``) vs the
    overlap-aware versioned publish, under a slow-actor regime where the
    actor consumes one publish in ``consume_every`` learner updates.  This
    is the regime a fast accelerator learner sits in (sub-ms updates,
    actors busy stepping envs); when actors consume every publish no skip
    triggers and both policies transfer identically.  On one device the
    actor core is the learner core: a publish there moves no bytes and is
    never skipped, so both policies count the same.

``BENCH_learner.json`` schema:

    {"update": {"batch_<B>": {
         "legacy_us_per_update", "legacy_updates_per_s",
         "fused_us_per_update", "fused_updates_per_s", "speedup",
         "update_in_place": bool, "legacy_alloc_bytes_per_update",
         "fused_alloc_bytes_per_update", "actor_batch",
         "trajectory_length", "updates_per_window"}},
     "publish": {"actor_batch", "updates", "consume_every",
                 "legacy_transfers", "legacy_skipped", "legacy_bytes",
                 "throttled_transfers", "throttled_skipped",
                 "throttled_bytes", "param_bytes", "transfer_ratio"}}

(us/speedup fields are wall-clock and noisy on CPU; the ``*_alloc_bytes``
/ ``update_in_place`` / transfer-count fields are deterministic and are
the regression signal.)
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._timing import csv_line

BATCHES = (4, 32)
TRAJ = 20
UPDATES = 20  # learner updates per timed window


def _build(batch: int, **cfg_overrides):
    from repro import optim
    from repro.agents.impala import ConvActorCritic
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.envs import HostPong

    net = ConvActorCritic(HostPong.num_actions, channels=(8,), blocks=1,
                          hidden=64)
    seb = Sebulba(
        env_factory=lambda seed: HostPong(seed=seed),
        make_batched_env=lambda f, n: None,  # learner-only: no actor threads
        network=net,
        optimizer=optim.rmsprop(2e-4, clip_norm=1.0),
        config=SebulbaConfig(
            num_actor_cores=1, threads_per_actor_core=1,
            actor_batch_size=batch, trajectory_length=TRAJ,
            **cfg_overrides,
        ),
    )
    params, opt_state = seb.init(jax.random.key(0), (16, 16, 1))
    return seb, params, opt_state


def _make_traj(seb, batch: int, seed: int):
    """A synthetic learner-sharded trajectory batch (same structure the
    actor drain produces)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.trajectory import Trajectory

    rng = np.random.RandomState(seed)
    sharding = NamedSharding(seb.learner_mesh, P("batch"))
    traj = Trajectory(
        obs=rng.rand(batch, TRAJ, 16, 16, 1).astype(np.float32),
        actions=rng.randint(0, 3, (batch, TRAJ)).astype(np.int32),
        rewards=rng.rand(batch, TRAJ).astype(np.float32),
        discounts=np.full((batch, TRAJ), 0.99, np.float32),
        behaviour_logp=np.log(
            rng.uniform(0.2, 0.9, (batch, TRAJ))
        ).astype(np.float32),
        bootstrap_obs=rng.rand(batch, 16, 16, 1).astype(np.float32),
    )
    return jax.tree.map(lambda x: jax.device_put(x, sharding), traj)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def bench_update(batch: int, updates: int = UPDATES, reps: int = 8) -> dict:
    """-> {legacy_us_per_update, fused_us_per_update, speedup, ...}.

    Both variants run the identical compiled math; the timed windows
    differ only in the pipeline around it (donation + accumulator).  Each
    window chains ``updates`` learner updates; windows alternate variant
    order every rep so container load drift hits both equally, and the
    per-variant minimum over all windows estimates the true floor.  Both
    windows get identical allocation churn (fresh trajectory copies built
    before the clock starts) so the donated variant's consumed inputs
    don't bias cache state.
    """
    seb, params0, opt0 = _build(batch)
    example = _make_traj(seb, batch, 0)

    # the pre-PR program: the identical shard_map'd update core, jitted
    # with no donation (this IS what `jax.jit(self._build_update())` ran)
    legacy = jax.jit(seb._build_update(example))
    fused, core = seb._get_update(example)
    macc0 = seb._fresh_macc(
        jax.eval_shape(core, params0, opt0, example)[2]
    )

    trajs = [_make_traj(seb, batch, 1 + i) for i in range(updates)]

    # compile both OUTSIDE the timed windows (donated warmup consumes its
    # inputs, so it gets private copies)
    jax.block_until_ready(legacy(params0, opt0, trajs[0]))
    jax.block_until_ready(
        fused(_copy(params0), _copy(opt0), _make_traj(seb, batch, 999),
              _copy(macc0))
    )

    def run_legacy() -> float:
        p, o = _copy(params0), _copy(opt0)
        fresh = [jax.tree.map(jnp.copy, t) for t in trajs]
        jax.block_until_ready((p, o, fresh))
        t0 = time.perf_counter()
        for traj in fresh:
            p, o, metrics = legacy(p, o, traj)
        jax.block_until_ready((p, metrics))
        return time.perf_counter() - t0

    def run_fused() -> float:
        p, o, macc = _copy(params0), _copy(opt0), _copy(macc0)
        fresh = [jax.tree.map(jnp.copy, t) for t in trajs]
        jax.block_until_ready((p, o, macc, fresh))
        t0 = time.perf_counter()
        for traj in fresh:
            p, o, macc = fused(p, o, traj, macc)
        jax.block_until_ready((p, macc))
        return time.perf_counter() - t0

    # paired estimator: each rep times both variants back to back (order
    # alternating), so slow container-load drift is common-mode within a
    # pair; the median of per-pair ratios is the speedup, robust to drift
    # that a min-over-windows estimator conflates with the variants
    pairs = []
    best = {"legacy": float("inf"), "fused": float("inf")}
    for r in range(reps):
        if r % 2 == 0:
            l, f = run_legacy(), run_fused()
        else:
            f, l = run_fused(), run_legacy()
        pairs.append(l / f)
        best["legacy"] = min(best["legacy"], l)
        best["fused"] = min(best["fused"], f)
    results = {}
    for name in ("legacy", "fused"):
        us = best[name] / updates * 1e6
        results[f"{name}_us_per_update"] = round(us, 1)
        results[f"{name}_updates_per_s"] = round(1e6 / us, 2)
    results["speedup"] = round(float(np.median(pairs)), 3)

    # deterministic (noise-free) structural costs of one update: bytes the
    # pre-PR path allocates for its double-buffered outputs vs the donated
    # path, whose outputs take the donated state's storage (read from
    # buffer pointers).  Where the actor core is a learner core (one
    # device) params are not donated — the actors' slot holds them — so
    # one params set is fresh and the publish copies nothing.
    state_bytes = sum(
        leaf.nbytes for leaf in jax.tree.leaves((params0, opt0))
    )
    p, o, macc = _copy(params0), _copy(opt0), _copy(macc0)
    in_ptrs = {leaf.unsafe_buffer_pointer()
               for leaf in jax.tree.leaves((p, o))}
    p2, o2, _ = fused(p, o, _make_traj(seb, batch, 1000), macc)
    fresh_bytes = sum(
        leaf.nbytes for leaf in jax.tree.leaves((p2, o2))
        if leaf.unsafe_buffer_pointer() not in in_ptrs
    )
    results["update_in_place"] = fresh_bytes == 0
    results["legacy_alloc_bytes_per_update"] = state_bytes
    results["fused_alloc_bytes_per_update"] = fresh_bytes
    results["actor_batch"] = batch
    results["trajectory_length"] = TRAJ
    results["updates_per_window"] = updates
    return results


def bench_publish(batch: int = 32, updates: int = 32,
                  consume_every: int = 4) -> dict:
    """Publish transfers over ``updates`` learner updates, actor consuming
    one publish per ``consume_every`` updates -> before/after counts."""
    out = {"actor_batch": batch, "updates": updates,
           "consume_every": consume_every}
    for name, throttle in (("legacy", False), ("throttled", True)):
        seb, params, _ = _build(batch, publish_throttle=throttle)
        param_bytes = sum(l.nbytes for l in jax.tree.leaves(params))
        base_sent = seb.publishes_sent  # init's forced publish
        for u in range(updates):
            if u % consume_every == 0:
                # the simulated (slow) actor picks up its standing slot
                seb._slot_consumed[0] = seb._param_slots[0][0]
            seb._publish_params(params)
        sent = seb.publishes_sent - base_sent
        out[f"{name}_transfers"] = sent
        out[f"{name}_skipped"] = seb.publishes_skipped
        out[f"{name}_bytes"] = sent * param_bytes
    out["param_bytes"] = param_bytes
    out["transfer_ratio"] = round(
        out["legacy_transfers"] / max(out["throttled_transfers"], 1), 2
    )
    return out


def csv_lines(results: dict) -> list[str]:
    lines = []
    for key, upd in results["update"].items():
        b = upd["actor_batch"]
        lines.append(csv_line(
            f"learner_update_legacy_b{b}", upd["legacy_us_per_update"],
            f"updates_per_s={upd['legacy_updates_per_s']}"))
        lines.append(csv_line(
            f"learner_update_fused_b{b}", upd["fused_us_per_update"],
            f"updates_per_s={upd['fused_updates_per_s']} "
            f"speedup={upd['speedup']}x"))
    pub = results["publish"]
    lines.append(csv_line(
        "learner_publish_transfers", 0.0,
        f"legacy={pub['legacy_transfers']} "
        f"throttled={pub['throttled_transfers']} "
        f"ratio={pub['transfer_ratio']}x "
        f"bytes_saved={pub['legacy_bytes'] - pub['throttled_bytes']:,}"))
    return lines


def main(json_path: str | None = None) -> list[str]:
    # B=4 windows are short, so drift within a legacy/fused pair is the
    # noise floor — many short pairs beat few long ones there
    points = {4: dict(updates=12, reps=16), 32: dict(updates=20, reps=8)}
    results = {
        "update": {
            f"batch_{b}": bench_update(batch=b, **points[b]) for b in BATCHES
        },
        "publish": bench_publish(),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2)
    return csv_lines(results)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_learner.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for line in main(json_path="BENCH_learner.json" if args.json else None):
        print(line)
    if args.json:
        print("wrote BENCH_learner.json")
