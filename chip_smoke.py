"""Smoke test of the Podracer main path on TPU chips, through the entry
points a user calls.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the multi-chip path, on four chips

One chip, in order, each phase failing the run at its first fault:

  * device  — JAX must find a TPU; the script never runs on a CPU;
  * kernels — Pallas flash_decode (dense and paged) at qwen2-1.5b decode
    widths against the jnp oracle, Pallas V-trace against ``vtrace_ref``,
    each compiled program checked for the kernel (``tpu_custom_call``);
  * serve   — ``ServeEngine(paged=True)`` on qwen2-1.5b at its published
    widths and depth: 16 seeded requests, 32-256 prompt tokens, 32 new
    tokens each; one request's first prefill and decode logits against
    the plain forward;
  * impala  — Sebulba IMPALA on the device Pong fleet (actor batch 32,
    trajectory 20), actor and learner sharing the chip;
  * lm-rl   — ``LMPolicyAgent`` on Sebulba with ``TokenEnv``, qwen2-1.5b
    widths at a cut depth (the cut is printed).

``--chips 4`` runs only what exists across chips: Sebulba split 1 actor :
3 learners and Anakin replicated over 4 chips, each update checked against
the same update computed on one chip over the same batch.

Weights and data are random, made from ``--seed``.  The last line of
standard output is one JSON object naming the device.  Everything runs in
this one process, which holds the chips.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import optim  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

# LM-RL learner memory on one 16 GB v5e chip, from memory_analysis() of the
# compiled update at qwen2-1.5b widths: about 3.2 GiB + 0.37 GiB per layer,
# plus the actor's own params copy on the shared chip (about 0.47 GiB +
# 0.09 GiB per layer, twice while a publish lands).  All 28 layers come to
# about 14.8 GiB with one copy; 16 layers to about 11 GiB with two.
LM_RL_LAYERS = 16


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(
        jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32)
    )))


def assert_kernel(fn, *args) -> None:
    """The compiled program must contain the Pallas kernel: no jnp path
    stands in for it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    check("tpu_custom_call" in text, f"no Pallas kernel in {fn}")


# ------------------------------------------------------------------ device


def device_phase(chips: int) -> jax.Device:
    devs = jax.devices()
    dev = devs[0]
    check(
        dev.platform == "tpu",
        f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})",
    )
    check(len(devs) >= chips, f"needs {chips} chips, found {len(devs)}")
    log(f"device: {dev.device_kind} x {len(devs)}")
    return dev


# ----------------------------------------------------------------- kernels


def kernels_phase(cfg, seed: int, *, S: int = 4096) -> None:
    from repro.kernels.flash_decode.ops import flash_decode
    from repro.kernels.vtrace.ops import vtrace
    from repro.kernels.vtrace.ref import vtrace_ref

    B, bs = 8, 16
    H, K, h = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.key(seed), 8)
    q = jax.random.normal(ks[0], (B, 1, H, h), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (B, S, K, h), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (B, S, K, h), jnp.bfloat16)
    # ragged per-row positions, first and last cache slot included
    pos = jnp.asarray(
        np.r_[0, S - 1, np.random.default_rng(seed).integers(0, S, B - 2)],
        jnp.int32,
    )
    nb = S // bs
    kp = kc.reshape(B * nb, bs, K, h)
    vp = vc.reshape(B * nb, bs, K, h)
    tables = jax.random.permutation(ks[3], B * nb).reshape(B, nb)
    tables = tables.astype(jnp.int32)

    cases = {
        "flash_decode dense": (
            functools.partial(flash_decode, impl="pallas"),
            functools.partial(flash_decode, impl="jnp"),
            (q, kc, vc, pos),
        ),
        "flash_decode paged": (
            lambda q, k, v, p, t: flash_decode(
                q, k, v, p, block_tables=t, impl="pallas"),
            lambda q, k, v, p, t: flash_decode(
                q, k, v, p, block_tables=t, impl="jnp"),
            (q, kp, vp, pos, tables),
        ),
    }
    for name, (kernel, oracle, args) in cases.items():
        assert_kernel(kernel, *args)
        with jax.default_matmul_precision("float32"):
            ref = oracle(*args)
        err = max_err(kernel(*args), ref)
        log(f"kernels: {name} B={B} S={S} H={H} K={K} h={h}: "
            f"max abs err {err:.3e} (tol 3e-2)")
        check(err <= 3e-2, f"{name} parity {err}")

    for batch in (32, 256):
        T = 20
        kk = jax.random.split(ks[4 + batch % 3], 5)
        lr = 0.3 * jax.random.normal(kk[0], (batch, T))
        disc = 0.99 * (jax.random.uniform(kk[1], (batch, T)) > 0.1)
        rew = jax.random.normal(kk[2], (batch, T))
        val = jax.random.normal(kk[3], (batch, T))
        boot = jax.random.normal(kk[4], (batch,))
        args = (lr, disc.astype(jnp.float32), rew, val, boot)
        kernel = functools.partial(vtrace, impl="pallas")
        assert_kernel(kernel, *args)
        out, ref = kernel(*args), vtrace_ref(*args)
        err = max(max_err(out.vs, ref.vs),
                  max_err(out.pg_advantages, ref.pg_advantages))
        log(f"kernels: vtrace B={batch} T={T}: max abs err {err:.3e} "
            "(tol 1e-4)")
        check(err <= 1e-4, f"vtrace parity {err}")


# ------------------------------------------------------------------- serve


def serve_phase(cfg, seed: int, *, requests: int = 16, prompt=(32, 256),
                new_tokens: int = 32, rows: int = 8) -> None:
    from repro.models import make_model
    from repro.serve import Request, ServeConfig, ServeEngine

    model = make_model(cfg)
    params = jax.jit(model.init)(jax.random.key(seed))
    n = sum(x.size for x in jax.tree.leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"serve: {cfg.name} {cfg.num_layers}L d={cfg.d_model} "
        f"H={cfg.num_heads} K={cfg.num_kv_heads} vocab={cfg.vocab_size}: "
        f"{n / 1e9:.3f}B params, {nbytes / 1e9:.2f} GB")

    bs, chunk = 16, 64
    max_seq = -(-(prompt[1] + new_tokens) // bs) * bs
    scfg = ServeConfig(
        batch_rows=rows, prefill_chunk=chunk, token_budget=rows + chunk,
        block_size=bs, num_blocks=1 + rows * (max_seq // bs),
        max_seq=max_seq, temperature=0.0, seed=seed,
    )
    rng = np.random.default_rng(seed)
    reqs = [
        Request(
            rid=i + 1,
            prompt=tuple(int(t) for t in rng.integers(
                2, cfg.vocab_size, int(rng.integers(prompt[0], prompt[1] + 1))
            )),
            max_new_tokens=new_tokens,
        )
        for i in range(requests)
    ]
    engine = ServeEngine(model, params, scfg, paged=True)
    t0 = time.perf_counter()
    res = engine.run(reqs)
    wall = time.perf_counter() - t0
    outs = res["outputs"]
    check(res["completed"] == requests,
          f"{res['completed']} of {requests} requests completed")
    for r in reqs:
        check(len(outs[r.rid]) == new_tokens,
              f"request {r.rid} returned {len(outs[r.rid])} tokens")
    log(f"serve: {requests} requests, prompts {prompt[0]}-{prompt[1]} "
        f"tokens, {new_tokens} new tokens each: all completed; "
        f"{res['tokens_prefilled']} prefilled + {res['tokens_decoded']} "
        f"decoded tokens in {wall:.2f} s wall (compiles included), "
        f"{res['preempted']} preempted")

    # one request's first prefill and decode logits, through the serving
    # path (paged prefill, then the paged flash_decode step), against the
    # plain full-sequence forward over the same tokens
    req = reqs[0]
    p = list(req.prompt)
    first = outs[req.rid][0]
    n_blocks = -(-(len(p) + 1) // bs)
    cache, _ = model.init_paged_cache(1 + n_blocks, bs)
    table = jnp.arange(1, 1 + n_blocks, dtype=jnp.int32)[None]
    lg_pre, _, cache = jax.jit(model.prefill_step)(
        params, cache, jnp.asarray([p], jnp.int32),
        jnp.zeros((1,), jnp.int32), table,
    )
    lg_dec, _, _ = jax.jit(model.decode_step)(
        params, cache, jnp.asarray([[first]], jnp.int32),
        jnp.asarray([len(p)], jnp.int32), table,
    )
    ref, _, _ = jax.jit(model.forward)(
        params, {"tokens": jnp.asarray([p + [first]], jnp.int32)}
    )
    for name, got, want in (
        ("prefill (first step)", lg_pre[0, -1], ref[0, len(p) - 1]),
        ("decode (second step)", lg_dec[0, 0], ref[0, len(p)]),
    ):
        err = max_err(got, want)
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        agree = int(jnp.argmax(got)) == int(jnp.argmax(want))
        log(f"serve: request {req.rid} {name} logits vs plain forward: "
            f"max abs err {err:.3e}, max |logit| {scale:.3e}, "
            f"rel {err / scale:.3e} (tol 1e-1), top-1 agrees: {agree}")
        check(err <= 1e-1 * scale, f"{name} logits parity {err / scale}")
    log(f"serve: request {req.rid} engine's first token {first}, argmax of "
        f"the single-chunk prefill logits {int(jnp.argmax(lg_pre[0, -1]))}")


# ---------------------------------------------------------------- training


def check_fit(name: str, out: dict) -> None:
    loss = float(out["metrics"]["loss"])
    log(f"{name}: {out['updates']} updates, {out['frames']} frames in "
        f"{out['seconds']:.1f} s wall (compiles included), loss {loss:.4f}, "
        f"actor_restarts {out['actor_restarts']}, "
        f"actor_quarantined {out['actor_quarantined']}")
    check(out["updates"] > 0, f"{name}: no learner update")
    check(math.isfinite(loss), f"{name}: loss {loss}")
    check(out["actor_restarts"] == 0, f"{name}: actors restarted")
    check(out["actor_quarantined"] == 0, f"{name}: actors quarantined")


def impala_phase(seed: int, *, batch: int = 32, traj: int = 20,
                 trajectories: int = 10, devices=None) -> None:
    """``trajectories`` sets the frame budget; actors run ahead of the
    learner by the queue's depth, so updates come out fewer."""
    from repro.agents.impala import ConvActorCritic
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.envs import Pong

    seb = Sebulba(
        network=ConvActorCritic(Pong.num_actions, channels=(16, 32), blocks=1),
        optimizer=optim.rmsprop(3e-4, clip_norm=1.0),
        config=SebulbaConfig(
            num_actor_cores=1, threads_per_actor_core=2,
            actor_batch_size=batch, trajectory_length=traj,
        ),
        device_env=Pong,
        devices=devices,
    )
    out = seb.fit(jax.random.key(seed),
                  total_frames=batch * traj * trajectories)
    shared = seb.split.actor_devices == seb.split.learner_devices
    check_fit("impala (actor and learner share one chip)" if shared else
              f"impala ({seb.split.num_actors} actor : {seb.L} learner "
              "chips)", out)


def lm_rl_phase(cfg, seed: int, *, batch: int = 8, prompt_len: int = 8,
                trajectories: int = 12, devices=None) -> None:
    from repro.agents.lm_policy import LMPolicyAgent
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.envs import TokenEnv
    from repro.launch.steps import TrainHParams

    env = TokenEnv(vocab_size=cfg.vocab_size, prompt_len=prompt_len,
                   data_vocab=16)
    agent = LMPolicyAgent(
        cfg, max_seq=env.episode_len,
        hparams=TrainHParams(rl_weight=0.1, entropy_cost=0.003),
    )
    seb = Sebulba(
        agent=agent,
        device_env=env,
        optimizer=optim.adam(1e-5, clip_norm=1.0),
        config=SebulbaConfig(
            num_actor_cores=1, threads_per_actor_core=2,
            actor_batch_size=batch, trajectory_length=env.episode_len,
            # the first act step and learner update compile at full width;
            # the watchdog must not take that for a hung actor
            stall_timeout=900.0,
        ),
        devices=devices,
    )
    out = seb.fit(jax.random.key(seed),
                  total_frames=batch * env.episode_len * trajectories)
    check_fit(f"lm-rl ({cfg.num_layers}L d={cfg.d_model})", out)


# ------------------------------------------------------------- four chips


def param_delta_err(p0, p_test, p_ref) -> float:
    """|| (p_test - p0) - (p_ref - p0) || / || p_ref - p0 ||, over every
    leaf: how far one update lands from the reference update."""
    leaves = lambda t: [np.asarray(x, np.float64) for x in jax.tree.leaves(t)]
    num = sum(np.sum((a - b) ** 2) for a, b in zip(leaves(p_test), leaves(p_ref)))
    den = sum(np.sum((b - a) ** 2) for a, b in zip(leaves(p0), leaves(p_ref)))
    return float(np.sqrt(num / den))


def sebulba_multichip_phase(seed: int, devices) -> None:
    """1 actor : 3 learner chips.  A short fit drives the real path
    (actor on chip 0, trajectories shipped chip to chip, learner
    ``shard_map`` + ``pmean``); then one learner update over the 3-chip
    mesh is checked against the same update on one chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.agents.impala import ConvActorCritic
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.data.trajectory import Trajectory
    from repro.envs import Pong

    B, T = 48, 20
    opt = optim.sgd(1e-2)
    seb = Sebulba(
        network=ConvActorCritic(Pong.num_actions, channels=(16, 32), blocks=1),
        optimizer=opt,
        config=SebulbaConfig(num_actor_cores=1, threads_per_actor_core=2,
                             actor_batch_size=B, trajectory_length=T),
        device_env=Pong,
        devices=devices,
    )
    check(seb.L == 3, f"expected 3 learner chips, got {seb.L}")
    check_fit("sebulba 1:3", seb.fit(jax.random.key(seed),
                                     total_frames=B * T * 6))

    obs_shape = Pong().obs_shape
    params0, opt0 = seb.init(jax.random.key(seed + 1), obs_shape)
    p0 = jax.device_get(params0)
    rng = np.random.default_rng(seed)
    traj = Trajectory(
        obs=rng.random((B, T) + obs_shape, np.float32),
        actions=rng.integers(0, Pong.num_actions, (B, T)).astype(np.int32),
        rewards=rng.normal(size=(B, T)).astype(np.float32),
        discounts=np.full((B, T), 0.99, np.float32),
        behaviour_logp=np.log(rng.uniform(0.2, 0.9, (B, T))).astype(np.float32),
        bootstrap_obs=rng.random((B,) + obs_shape, np.float32),
    )
    sharded = jax.device_put(traj, NamedSharding(seb.learner_mesh, P("batch")))
    p3, _, _ = jax.jit(seb._build_update(sharded))(params0, opt0, sharded)

    def one_chip_update(params, traj):
        grads, _ = jax.grad(lambda p: seb.agent.loss(p, traj), has_aux=True)(
            params
        )
        updates, _ = opt.update(grads, opt.init(params), params)
        return optim.apply_updates(params, updates)

    dev = devices[0]
    p1 = jax.jit(one_chip_update)(jax.device_put(p0, dev),
                                  jax.device_put(traj, dev))
    err = param_delta_err(p0, p3, p1)
    log(f"sebulba 1:3: one learner update over 3 chips vs one chip, same "
        f"batch: relative update error {err:.3e} (tol 1e-2)")
    check(err <= 1e-2, f"sebulba 1:3 update parity {err}")


def anakin_multichip_phase(seed: int, devices) -> None:
    """Anakin replicated over 4 chips (``shard_map`` + ``pmean``) against
    Anakin on one chip holding the whole batch: same initial state, same
    per-env keys, one update."""
    from repro.agents.actor_critic import MLPActorCritic
    from repro.core.anakin import Anakin, AnakinConfig
    from repro.envs import Catch

    env = Catch()
    net = MLPActorCritic(env.num_actions, (64, 64))
    opt = optim.sgd(1e-2)
    per_chip = 64

    def make(devs):
        return Anakin(env, net, opt, AnakinConfig(
            unroll_length=10, batch_per_device=per_chip * 4 // len(devs),
            iterations_per_call=1,
        ), devices=devs)

    ank4, ank1 = make(devices), make(devices[:1])
    s4, s1 = ank4.init_state(jax.random.key(seed)), ank1.init_state(
        jax.random.key(seed))
    p0 = jax.device_get(s1.params)
    (s4, m4), (s1, _) = ank4.run(s4), ank1.run(s1)
    err = param_delta_err(p0, s4.params, s1.params)
    log(f"anakin x4: one update over 4 chips vs one chip, same batch: "
        f"relative update error {err:.3e} (tol 1e-2), loss "
        f"{float(m4['loss']):.4f}")
    check(err <= 1e-2, f"anakin x4 update parity {err}")
    check(math.isfinite(float(m4["loss"])), "anakin x4 loss")


def memory_report(devices) -> None:
    for d in devices:
        st = d.memory_stats() or {}
        log(f"memory: {d} bytes_in_use {st.get('bytes_in_use')} "
            f"peak_bytes_in_use {st.get('peak_bytes_in_use')}")


# -------------------------------------------------------------------- main


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    from repro.configs.base import get_config

    t0 = time.perf_counter()
    dev = device_phase(args.chips)
    if args.chips == 4:
        devices = jax.devices()[:4]
        sebulba_multichip_phase(args.seed, devices)
        anakin_multichip_phase(args.seed, devices)
        memory_report(devices)
    else:
        qwen2 = get_config("qwen2-1.5b")
        kernels_phase(qwen2, args.seed)
        serve_phase(qwen2, args.seed)
        memory_report([dev])
        gc.collect()
        impala_phase(args.seed)
        log(f"lm-rl: depth cut {qwen2.num_layers} -> {LM_RL_LAYERS} layers "
            "to fit learner, optimizer and actor params on one 16 GB chip; "
            "widths unchanged")
        lm_rl_phase(dataclasses.replace(qwen2, num_layers=LM_RL_LAYERS),
                    args.seed)
        memory_report([dev])
    log(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
