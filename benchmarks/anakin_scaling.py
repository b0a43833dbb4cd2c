"""Paper Fig. 4a — Anakin FPS as a function of device count.

The paper shows near-linear scaling 16 -> 128 TPU cores.  This container
has one physical CPU, so each point runs in a subprocess with
``--xla_force_host_platform_device_count=N`` placeholder devices: the point
is that the *same program* replicates across N devices with one config
change (the paper's claim), and the per-device work stays constant.  On
shared-CPU placeholders wall-clock FPS cannot exceed 1x, so we report both
raw FPS and per-device efficiency; real-hardware scaling is projected in
EXPERIMENTS.md from the collective-term roofline.

Output: ``anakin_scale_<N>dev`` CSV lines (us/step + fps/efficiency in the
derived column); no BENCH json — the scaling figure is a paper-shape
check, not a regression trajectory.  Honest timing: each subprocess warms
its compiled step before its timed window, so jit compile never lands in
a measurement (the shared rule for every suite in this directory).
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
    import time, jax
    from repro.core.anakin import Anakin, AnakinConfig
    from repro.agents.actor_critic import MLPActorCritic
    from repro.envs import Catch
    from repro import optim

    env = Catch()
    net = MLPActorCritic(env.num_actions, (64, 64))
    ank = Anakin(env, net, optim.adam(3e-3, clip_norm=1.0),
                 AnakinConfig(unroll_length=10, batch_per_device=32,
                              iterations_per_call=20))
    state = ank.init_state(jax.random.key(0))
    state, _ = ank.run(state)  # compile
    jax.block_until_ready(state)
    t0 = time.time()
    calls = 3
    for _ in range(calls):
        state, _ = ank.run(state)
    jax.block_until_ready(state)
    dt = time.time() - t0
    print("RESULT", ank.steps_per_call * calls / dt)
    """
)


def measure(n_devices: int) -> float:
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # a CPU study on placeholder devices by design: the child never
    # reaches for an accelerator this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(n=n_devices, src=src)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    for line in out.stdout.splitlines():
        if line.startswith("RESULT"):
            return float(line.split()[1])
    raise RuntimeError("no result line")


def main(device_counts=(1, 2, 4, 8)) -> list[str]:
    lines = []
    base = None
    for n in device_counts:
        fps = measure(n)
        base = base or fps
        lines.append(
            f"anakin_scaling_d{n},{1e6 / fps:.3f},"
            f"platform=cpu fps={fps:,.0f} rel={fps / base:.2f} per_dev={fps / n:,.0f}"
        )
        print(lines[-1], flush=True)
    return lines


if __name__ == "__main__":
    main()
