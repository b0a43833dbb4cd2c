"""The granite-4.0-h-micro cell: it resolves by name, its configuration
module counts the parameters, the act step's bytes and the SSD kernel's
cost as a hand count does, its reference is the program's model at a
small size, and a small run of the cell on the CPU is ``correct``.

The small cell is described here, with its own overrides: two Mamba-2
layers around one attention layer, every width cut (the chip runs the
published widths)."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common
from bench.drivers import sebulba as drv

NAME = "lmrl-granite-b64"
TINY = {
    "cfg": {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 128,
            "shared_intermediate_size": 128, "mamba_n_heads": 8,
            "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 8,
            "num_hidden_layers": 3,
            "layer_types": ["mamba", "attention", "mamba"],
            "vocab_size": 256, "stall_timeout_s": 300.0},
    "traffic": {"prompt_len": 2, "trajectory_length": 4,
                "actor_batch_size": 8},
    "spec": {"warm_updates": 5},
}


def tiny():
    return common.Cell(NAME, overrides={k: dict(v) for k, v in TINY.items()})


def test_cell_resolves():
    cell = common.Cell(NAME)
    assert cell.chips == 1 and cell.entry["traffic"] == "token-copy8-b64"
    assert cell.cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    names = {m["name"] for m in cell.per_layer}
    assert "ssd_scan_roofline" in names and "allreduce_ms" not in names
    for fn in ("program_parts", "make_params", "forward", "loss",
               "flops_per_frame", "act_step_cost", "param_count",
               "ssd_scan_cost"):
        assert callable(getattr(cell.cfg_module, fn)), fn


def test_param_count_by_hand():
    """9 Mamba-2 mixers (input norm 2,048; in_proj 2,048 x 8,512 for z, x,
    B, C and dt; conv 4 x 4,352 and its bias; A_log, dt_bias, D for 64
    heads; the gated norm over 4,096; out_proj 4,096 x 2,048), one
    attention layer (norm; 32 query and 8 kv heads of 64, no bias), ten
    MLPs (norm; 3 x 2,048 x 8,192), the tied embedding, the final norm and
    the value head."""
    cell = common.Cell(NAME)
    d = 2048
    mamba = d + d * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * d
    attn = d + d * 32 * 64 + 2 * d * 8 * 64 + 32 * 64 * d
    mlp = d + 3 * d * 8192
    assert (mamba, attn, mlp) == (25_849_280, 10_487_808, 50_333_696)
    total = 9 * mamba + attn + 10 * mlp + 100_352 * d + d + d
    assert total == 951_993_280
    assert cell.cfg_module.param_count(cell.cfg) == total


def test_param_layout_is_the_programs():
    """The benchmark's weights have the program's tree, shapes and dtypes."""
    cell = tiny()
    parts, _ = cell.cfg_module.program_parts(cell.cfg, cell.traffic)
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        parts["agent"].model.abstract()[0])
    got = jax.tree.map(lambda a: (a.shape, a.dtype),
                       jax.eval_shape(lambda k: drv.make_params(cell, 0), 0))
    assert got == want


def test_act_step_cost_by_hand():
    """64 rows: the bfloat16 weights (float32 norms, conv bias and SSM
    scalars) read once; per row, in each of 9 Mamba-2 layers, the float32
    state (64 heads x 64 x 128) and the bfloat16 conv window (3 x 4,352)
    read and written; the attention layer's keys and values (8 heads of
    64, bfloat16) read at the mean 8.5 live positions and one written."""
    cell = common.Cell(NAME)
    mod = cell.cfg_module
    d = 2048
    f32 = (9 * (d + 4352 + 3 * 64 + 4096) + d + 10 * d + d)
    wbytes = 2 * (mod.param_count(cell.cfg) - f32) + 4 * f32
    state = 9 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    kv = 2 * 8 * 64 * 2
    flops, nbytes = mod.act_step_cost(cell.cfg, cell.traffic)
    assert nbytes == wbytes + 64 * (2 * state + kv * 9.5)
    assert nbytes == pytest.approx(4.35e9, rel=0.01)
    # per token: 2 per weight in a product; 4 x 32 x 64 x 8.5 in the
    # attention layer; per Mamba-2 layer the conv (2 x 4 x 4,352) and the
    # recurrence (4 x 64 x 64 x 128)
    matmul = (9 * (d * 8512 + 4096 * d) + 2 * d * 32 * 64 + 2 * d * 8 * 64
              + 10 * 3 * d * 8192 + 100_352 * d + d)
    token = 2 * matmul + 4 * 32 * 64 * 8.5 + 9 * (2 * 4 * 4352 + 4 * 64 * 64 * 128)
    assert flops == pytest.approx(64 * token, rel=1e-12)


def test_ssd_scan_cost_by_hand():
    """One learner call: 64 rows of 17 tokens in one chunk, 64 heads of 64,
    state 128."""
    cell = common.Cell(NAME)
    B, T, H, P, N = 64, 17, 64, 64, 128
    flops, nbytes = cell.cfg_module.ssd_scan_cost(cell.cfg, cell.traffic)
    per_head = 2 * T * T * N + 2 * T * T * P + 2 * T * P * N + 2 * T * P * N
    assert flops == B * H * per_head
    # x in and y out (bf16), dt (f32), B and C (bf16), the final state
    # (f32) out, A (f32)
    assert nbytes == (2 * B * T * H * P * 2 + B * T * H * 4 + 2 * B * T * N * 2
                      + B * H * P * N * 4 + H * 4)


def test_reference_is_the_programs_model():
    """At a small size in float32 the reference's logits, values, loss and
    gradient are the program's, to float32 rounding."""
    from bench.tests.test_reference import as_program_traj, random_traj

    cell = tiny()
    parts, _ = cell.cfg_module.program_parts(cell.cfg, cell.traffic)
    agent = parts["agent"]
    from repro.models.model import make_model

    agent.cfg = dataclasses.replace(agent.cfg, param_dtype="float32")
    agent.model = make_model(agent.cfg, unroll=True)
    mod, V = cell.cfg_module, cell.cfg["vocab_size"]
    p = jax.tree.map(lambda x: x.astype(jnp.float32), drv.make_params(cell, 5))
    tokens = jax.random.randint(jax.random.key(1), (3, 11), 0, V)
    want_l, want_v = mod.forward(p, tokens, cell.cfg)
    got_l, got_v, _ = agent.model.forward(p, {"tokens": tokens})
    np.testing.assert_allclose(got_l, want_l, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(got_v, want_v, rtol=2e-4, atol=2e-5)

    tok = lambda k, s: jax.random.randint(k, s, 0, V)
    t = random_traj(jax.random.key(2), 4, 8, tok, V)
    want, _ = mod.loss(p, t, cell.cfg)
    got, _ = agent.loss(p, as_program_traj(t))
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    gw = jax.grad(lambda q: mod.loss(q, t, cell.cfg)[0])(p)
    gg = jax.grad(lambda q: agent.loss(q, as_program_traj(t))[0])(p)
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6)


def test_small_run_is_correct():
    """The cell's run on the CPU at the small size, through set-up, a
    window and the reference's three updates, is ``correct`` under the
    cell's own limits."""
    cell = tiny()
    result, checks = cell.driver.run(cell, 2_300_000_017, 1.0, False,
                                     jax.devices()[:1], time.perf_counter())
    assert result["correct"] is True, checks
    assert result["metrics"]["train_frames_per_s"]["value"] > 0
