"""Paper Fig. 4c — Sebulba/MuZero FPS as a function of device count.

The paper reports linear FPS scaling for search-based agents.  Points run
in subprocesses with N placeholder devices, each with a fixed 1:3
actor:learner core ratio; FPS trend across replicas is the reproduced
quantity.

Output: ``muzero_scale_<N>dev`` CSV lines; no BENCH json (paper-shape
check, not a regression trajectory).  Honest timing: FPS is whole-run
wall-clock over a fixed frame budget measured inside the subprocess, with
the first trajectory's compile cost amortized by the budget — comparisons
are valid across device counts because every point pays it identically.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    import sys; sys.path.insert(0, {src!r})
    import jax
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.agents.muzero import MuZeroAgent, MuZeroConfig
    from repro.envs import HostPong, BatchedHostEnv
    from repro import optim

    agent = MuZeroAgent(HostPong.num_actions,
                        MuZeroConfig(num_simulations=8, max_depth=4,
                                     unroll_steps=3))
    seb = Sebulba(
        env_factory=lambda seed: HostPong(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        optimizer=optim.adam(1e-3, clip_norm=1.0), agent=agent,
        config=SebulbaConfig(num_actor_cores=max(1, {n} // 4),
                             threads_per_actor_core=2,
                             actor_batch_size=12, trajectory_length=12,
                             learner_microbatches=2),
    )
    out = seb.run(jax.random.key(0), (16, 16, 1), total_frames={frames})
    print("RESULT", out["fps"])
    """
)


def measure(n_devices: int, frames: int = 3_000) -> float:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # a CPU study on placeholder devices by design: the child never
    # reaches for an accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(n=n_devices, frames=frames,
                                              src=src)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    for line in out.stdout.splitlines():
        if line.startswith("RESULT"):
            return float(line.split()[1])
    raise RuntimeError("no result line")


def main(device_counts=(4, 8)) -> list[str]:
    lines = []
    for n in device_counts:
        fps = measure(n)
        lines.append(f"muzero_scaling_d{n},{1e6 / fps:.3f},platform=cpu fps={fps:,.0f}")
        print(lines[-1], flush=True)
    return lines


if __name__ == "__main__":
    main()
