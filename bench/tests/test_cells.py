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
           "grad_gap_median", "change_gap_median")
WORKLOADS = sorted(p.stem for p in (common.BENCH / "workloads").glob("*.json"))


def test_every_workload_file_is_a_cell():
    assert WORKLOADS == sorted(w["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_resolves(name):
    cell = common.Cell(name)
    assert cell.spec["name"] == name
    assert cell.chips in (1, 4)
    assert callable(cell.driver.run)
    for fn in ("make_params", "forward", "loss", "flops_per_frame"):
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
    """The reduced keys are depth only; the shapes the reference and the
    weights use come from the configuration file."""
    for c in BENCH["configs"]:
        cfg = json.loads((common.ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        for key in c["reduced"]:
            assert not re.search(r"(size|_dim|_rank|width|heads)$", key), key
    qwen = common.Cell("lmrl-qwen2-copy64")
    n = qwen.cfg_module.param_count(qwen.cfg)
    assert math.isclose(n, 0.982e9, rel_tol=0.01), n
    impala = common.load_module(common.BENCH / "configs" / "impala-deep-pong84.py")
    cfg = common.load_json(common.BENCH / "configs" / "impala-deep-pong84.json")
    assert impala.param_count(cfg) == 1_089_828
    assert impala.frame_flops(cfg) == pytest.approx(102.4e6, rel=0.01)


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
