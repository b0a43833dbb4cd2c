"""The benchmark's own tests, run by hand on the CPU:

    python -m pytest bench/tests

They see four placeholder CPU devices, so the four-chip cell's learner
mesh exists; the program runs its jnp paths where the chip would run its
Pallas kernels.  ``TINY`` shrinks each cell to a size a test can hold.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

TINY_QWEN2 = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
              "stall_timeout_s": 300.0}
TINY = {
    "lmrl-qwen2-copy64": {
        "cfg": TINY_QWEN2,
        "traffic": {"prompt_len": 4, "trajectory_length": 8,
                    "actor_batch_size": 4},
        "spec": {"warm_updates": 5},
    },
    "lmrl-qwen2-learn": {
        "cfg": TINY_QWEN2,
        "traffic": {"prompt_len": 2, "trajectory_length": 4,
                    "actor_batch_size": 8},
        "spec": {"warm_updates": 5},
    },
    "impala-deep-pong84-x4": {
        "cfg": {"channels": [4, 8], "frame_height": 16, "frame_width": 16,
                "hidden": 32, "blocks_per_stage": 1, "stall_timeout_s": 300.0},
        "traffic": {"actor_batch_size": 6, "trajectory_length": 5},
        "spec": {"warm_updates": 5},
    },
}


@pytest.fixture
def tiny_cell():
    from bench import common

    def make(name, **spec):
        over = {k: dict(v) for k, v in TINY[name].items()}
        over["spec"].update(spec)
        return common.Cell(name, overrides=over)

    return make


@pytest.fixture
def cpu_devices():
    def pick(cell):
        return jax.devices()[: cell.chips]

    return pick
