"""Sebulba's profiler spans and its policy-lag counters, on the CPU.

A tiny LM-RL Sebulba (the benchmark's qwen2 cell shrunk to two layers of
width 64 over a 256-token vocabulary) trains under ``jax.profiler`` on
one device and, in a subprocess, on four placeholder devices (one actor
core, three learner cores).  The capture is reduced with the benchmark's
own ``bench.devtrace.Trace.from_dir``; the spans' stats, which the
reduction does not keep, are read from the capture itself.

Also pinned: the act step and the update lower to programs named
``jit__device_act_step_fn`` and ``jit_update``, the names the benchmark's
readers find them by.
"""

import argparse
import contextlib
import dataclasses
import glob
import io
import json
import os
import subprocess
import sys

SPANS = (
    "sebulba.actor.put",
    "sebulba.learner.get",
    "sebulba.learner.update",
    "sebulba.learner.publish",
    "sebulba.learner.checkpoint",
    "sebulba.learner.log",
)
COUNTERS = ("updates", "param_version", "policy_lag_mean", "policy_lag_max")


def tiny_sebulba(**config):
    """``config``: ``SebulbaConfig`` fields beside the trajectory length."""
    import jax

    from repro import optim
    from repro.agents.lm_policy import LMPolicyAgent
    from repro.configs.base import get_config
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.envs import TokenEnv

    arch = dataclasses.replace(
        get_config("qwen2-1.5b"), num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256, remat="none",
        param_dtype="float32", cache_dtype="float32",
    )
    env = TokenEnv(vocab_size=256, prompt_len=4, data_vocab=16)
    return Sebulba(
        optimizer=optim.adam(1e-3),
        config=SebulbaConfig(trajectory_length=env.episode_len,
                             stall_timeout=300.0, **config),
        agent=LMPolicyAgent(arch, max_seq=env.episode_len),
        device_env=env,
        devices=jax.devices(),
    )


def traced_fit(work_dir: str, *, updates: int = 8, **kw) -> dict:
    """Fit a tiny Sebulba for about ``updates`` updates with the profiler
    on from before the fit to after it, and summarise the capture: every
    ``sebulba.*`` span of the reduced trace with its thread and times, the
    stats of each from the capture, the result's counters and the log
    lines."""
    import jax
    from jax.profiler import ProfileData

    from bench.devtrace import Trace

    seb = tiny_sebulba(**kw)
    per_traj = seb.cfg.actor_batch_size * seb.cfg.trajectory_length
    log_dir = os.path.join(work_dir, "trace")
    out = io.StringIO()
    jax.profiler.start_trace(log_dir)
    try:
        with contextlib.redirect_stdout(out):
            result = seb.fit(
                jax.random.key(0), updates * per_traj, log_every=2,
                checkpoint_dir=os.path.join(work_dir, "ckpt"),
                checkpoint_every=3,
            )
    finally:
        jax.profiler.stop_trace()
    spans = sorted(
        (s, e, thread, name) for thread, name, s, e in
        Trace.from_dir(log_dir).host if name.startswith("sebulba.")
    )
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    stats = [
        {**{k: v for k, v in e.stats}, "name": e.name}
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:CPU")
        for line in plane.lines for e in line.events
        if e.name.startswith("sebulba.")
    ]
    return {
        "spans": spans,
        "stats": stats,
        "result": {k: result[k] for k in COUNTERS},
        "log": out.getvalue().splitlines(),
    }


def check_summary(summary: dict) -> None:
    spans, result = summary["spans"], summary["result"]
    names = [name for _, _, _, name in spans]
    for span in SPANS:
        assert span in names, f"no {span} span"
    # the learner thread: every update span is followed by its publish
    learner = [n for n in names if n.startswith("sebulba.learner.")
               and n != "sebulba.learner.get"]
    for i, name in enumerate(learner):
        if name == "sebulba.learner.update":
            assert learner[i + 1] == "sebulba.learner.publish", learner
    # the capture holds the whole fit: one update span per update
    assert names.count("sebulba.learner.update") == result["updates"] > 0

    by_name = {}
    for st in summary["stats"]:
        by_name.setdefault(st["name"], []).append(st)
    puts, ups = by_name["sebulba.actor.put"], by_name["sebulba.learner.update"]
    # one trajectory's spans share its id, from the actor's put to the
    # learner's update, and the update carries the version it was acted
    # with and the lag the result counts
    put_version = {p["traj"]: p["version"] for p in puts}
    for u in ups:
        assert put_version[u["traj"]] == u["version"], u
    lags = [u["lag"] for u in ups]
    assert min(lags) >= 0
    assert max(lags) == result["policy_lag_max"]
    assert sum(lags) / len(lags) == result["policy_lag_mean"]
    for p in by_name["sebulba.learner.publish"]:
        assert {"version", "sent", "skipped"} <= set(p), p
    assert any("policy_lag=" in line for line in summary["log"])


def test_spans_and_lag_on_one_device(tmp_path):
    """One actor thread behind a queue of capacity Q, every update
    published.  The first step of trajectory k acts with the params the
    actor read before it put trajectory k-1.  By then its put of k-2 had
    returned, so the learner had taken k-2-Q and published every update
    before that one: at most Q + 2 updates (k-2-Q .. k-1) come between
    the params it acted with and its own update.  With the publish
    throttle on, the bound would also depend on how the threads are
    scheduled: a publish is skipped while the actor is blocked."""
    summary = traced_fit(str(tmp_path), num_actor_cores=1,
                         threads_per_actor_core=1, actor_batch_size=4,
                         queue_capacity=1, publish_throttle=False)
    check_summary(summary)
    assert 0 <= summary["result"]["policy_lag_max"] <= 1 + 2


def test_spans_on_four_devices(tmp_path):
    """The same spans over one actor core and three learner cores, two
    actor threads, in a subprocess that sees four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = tmp_path / "summary.json"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--work-dir",
         str(tmp_path), "--out", str(out)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    summary = json.loads(out.read_text())
    assert summary["devices"] == 4
    check_summary(summary)


def test_program_names():
    """The act step and the update keep the program names the benchmark's
    readers match: ``jit__device_act_step_fn`` and ``jit_update``."""
    import jax
    import jax.numpy as jnp

    from repro.data.trajectory import buffer_drain

    seb = tiny_sebulba(num_actor_cores=1, actor_batch_size=4)
    params, opt_state = seb.init(jax.random.key(0), seb._fleet.obs_shape)
    fleet, dev = seb._fleet, seb.split.actor_devices[0]
    env_state = fleet.init(jax.random.key(1))
    obs = fleet.observe(env_state)
    buf = seb._make_actor_buffer(params, obs, dev)
    rew_disc = jnp.zeros((2, seb.cfg.actor_batch_size), jnp.float32)
    carry = seb._initial_carry(dev)
    act = seb._device_act_step.lower(
        params, buf, jax.random.key(2), env_state, obs, rew_disc, carry,
        fleet.init_stats(),
    )
    assert act.as_text().startswith("module @jit__device_act_step_fn")

    traj, _ = jax.eval_shape(buffer_drain, buf, rew_disc, obs)
    update, core = seb._get_update(traj)
    macc = seb._fresh_macc(jax.eval_shape(core, params, opt_state, traj)[2])
    lowered = update.lower(params, opt_state, traj, macc)
    assert lowered.as_text().startswith("module @jit_update")


def _main() -> None:
    ap = argparse.ArgumentParser(description="one traced tiny Sebulba fit")
    ap.add_argument("--work-dir", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax

    summary = traced_fit(args.work_dir, num_actor_cores=1,
                         threads_per_actor_core=2, actor_batch_size=6,
                         queue_capacity=2)
    summary["devices"] = len(jax.devices())
    with open(args.out, "w") as f:
        json.dump(summary, f)


if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    _main()
