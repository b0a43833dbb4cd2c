"""Every cell is found by name: its workload file, configuration, traffic
mix, driver and per-layer readers resolve from BENCHMARK.json alone, so a
cell is added with data files and no edit."""

import json
import math
import re

import pytest

from bench import common

BENCH = json.loads((common.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
NUMBERS = ("logp_gap", "loss_gap", "grad_gap", "change_gap",
           "grad_gap_median", "change_gap_median", "signed_change_gap_median")
WORKLOADS = sorted(p.stem for p in (common.BENCH / "workloads").glob("*.json"))


def test_every_workload_file_is_a_cell():
    assert WORKLOADS == sorted(w["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_resolves(name):
    cell = common.Cell(name)
    assert cell.spec["name"] == name
    assert cell.chips in (1, 4)
    assert callable(cell.driver.run)
    for fn in ("program_parts", "make_params", "forward", "loss",
               "flops_per_frame"):
        assert callable(getattr(cell.cfg_module, fn)), fn
    assert set(cell.spec["limits"]) <= set(NUMBERS)
    assert "logp_gap" in cell.spec["limits"]
    assert all(v > 0 for v in cell.spec["limits"].values())
    assert cell.cfg_module.flops_per_frame(cell.cfg, cell.traffic) > 0
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(cell.metric_reader(m["name"]).read)


def test_benchmark_contract_shapes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert (common.ROOT / c["file"]).exists()
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert len(w["why"]) <= 200
        assert (common.BENCH / "traffic" / f"{w['traffic']}.json").exists()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert (common.BENCH / "metrics" / f"{m['name']}.py").exists()
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    full = 2 + 14 * 24
    assert full * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs_keep_published_widths():
    """No reduced key is a width; the shapes the reference and the weights
    use come from the configuration file."""
    for c in BENCH["configs"]:
        cfg = json.loads((common.ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        for key in c["reduced"]:
            assert not re.search(r"(size|_dim|_rank|width|heads)$", key), key
    qwen = common.Cell("lmrl-qwen2-copy64")
    n = qwen.cfg_module.param_count(qwen.cfg)
    assert math.isclose(n, 0.982e9, rel_tol=0.01), n
    x4 = common.Cell("impala-deep-pong84-x4")
    # 1,089,828 on one frame, and 3 x 3 x 3 x 16 more first-conv weights
    # for the three further frames of the stack
    assert x4.cfg_module.param_count(x4.cfg) == 1_089_828 + 432 == 1_090_260
    assert x4.cfg_module.frame_flops(x4.cfg) == pytest.approx(108.5e6, rel=0.001)


def conv_flops(hw, cin, cout):
    return 2 * hw * hw * 9 * cin * cout


def test_impala_act_step_cost():
    """The IMPALA act step's least work, against a hand count: the deep
    network's convolutions on a stack of 4 frames (84 -> 42 -> 21 -> 11
    after each pool, two residual blocks of two convs a stage) and dense
    layers for each of the 96 frames, and the float32 weights plus one
    float32 batch of stacked frames."""
    x4 = common.Cell("impala-deep-pong84-x4")
    frame = (conv_flops(84, 4, 16) + 4 * conv_flops(42, 16, 16)
             + conv_flops(42, 16, 32) + 4 * conv_flops(21, 32, 32)
             + conv_flops(21, 32, 32) + 4 * conv_flops(11, 32, 32)
             + 2 * (11 * 11 * 32 * 256 + 256 * 3 + 256 * 1))
    flops, nbytes = x4.cfg_module.act_step_cost(x4.cfg, x4.traffic)
    assert x4.traffic["actor_batch_size"] == 96
    assert flops == 96 * frame
    # 4.36 MB of weights and 10.8 MB of frames
    assert nbytes == 4 * 1_090_260 + 96 * 84 * 84 * 4 * 4


def test_qwen2_builds_the_parents_arch_config():
    """The Qwen2 module builds, field for field, the ArchConfig, env and
    loss settings that the driver built before configurations built their
    own parts, so both LM cells compile the programs they compiled."""
    from repro.configs.base import ArchConfig
    from repro.launch.steps import TrainHParams

    for name in ("lmrl-qwen2-copy64", "lmrl-qwen2-learn"):
        cell = common.Cell(name)
        cfg, traffic = cell.cfg, cell.traffic
        parts, loss_kw = cell.cfg_module.program_parts(cfg, traffic)
        agent, env = parts["agent"], parts["device_env"]
        assert set(parts) == {"agent", "device_env"} and loss_kw == {}
        assert agent.cfg == ArchConfig(
            name="qwen2-1.5b-d16", family="dense",
            source="https://huggingface.co/Qwen/Qwen2-1.5B", num_layers=16,
            d_model=1536, num_heads=12, num_kv_heads=2, d_ff=8960,
            vocab_size=151936, head_dim=128, qkv_bias=True,
            rope_theta=1000000.0, rms_norm_eps=1e-06, tie_embeddings=True,
        )
        assert agent.hp == TrainHParams(rl_weight=0.1, entropy_cost=0.003,
                                        value_cost=0.5, aux_weight=0.01)
        assert (env.num_actions, env.prompt_len, env.data_vocab, env.task) == (
            151936, traffic["prompt_len"], 16, "copy")
        assert agent.max_seq == env.episode_len == traffic["trajectory_length"]


def test_cpu_run_is_refused():
    """A run that finds no TPU exits non-zero and names what it found."""
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, str(common.BENCH / "run.py"), "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=common.ROOT, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "no TPU" in r.stderr
    assert r.stdout.strip() == ""
