"""Paper Fig. 4b — Sebulba FPS as a function of actor batch size.

The paper scales actor batch 32 -> 128 on an 8-core TPU and reaches 200K
FPS.  Here the same sweep runs on 8 placeholder CPU devices (2 actor + 6
learner cores) at reduced batches; the figure of merit is the TREND (bigger
actor batches amortize per-step host/device overhead), which reproduces.

Output: ``sebulba_batch_<B>`` CSV lines; ``measure(batch, frames)`` is also
the end-to-end FPS point ``--suite sebulba`` records in
``BENCH_sebulba.json`` (key ``e2e``).  Honest timing: FPS is whole-run
wall-clock over a fixed frame budget in a fresh subprocess — compile cost
is inside the budget but identical across batch points, so the trend is
compile-neutral.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, {src!r})
    import jax
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.agents.impala import ConvActorCritic
    from repro.envs import HostPong, BatchedHostEnv
    from repro import optim

    net = ConvActorCritic(HostPong.num_actions, channels=(8,), blocks=1,
                          hidden=64)
    seb = Sebulba(
        env_factory=lambda seed: HostPong(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        network=net, optimizer=optim.rmsprop(2e-4, clip_norm=1.0),
        config=SebulbaConfig(num_actor_cores=2, threads_per_actor_core=2,
                             actor_batch_size={batch},
                             trajectory_length=20),
    )
    out = seb.run(jax.random.key(0), (16, 16, 1), total_frames={frames})
    print("RESULT", out["fps"], out["updates"])
    """
)


def measure(batch: int, frames: int = 20_000) -> float:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # a CPU study on placeholder devices by design: the child never
    # reaches for an accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(batch=batch, frames=frames,
                                              src=src)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    for line in out.stdout.splitlines():
        if line.startswith("RESULT"):
            return float(line.split()[1])
    raise RuntimeError("no result line")


def main(batches=(12, 24, 48)) -> list[str]:
    lines = []
    for b in batches:
        fps = measure(b)
        lines.append(f"sebulba_actor_batch_{b},{1e6 / fps:.3f},"
                     f"platform=cpu fps={fps:,.0f}")
        print(lines[-1], flush=True)
    return lines


if __name__ == "__main__":
    main()
