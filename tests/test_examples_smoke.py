"""Every examples/*.py entry point stays runnable (ISSUE 5 satellite).

Mirrors tests/test_benchmarks_import.py for the examples directory: until
now only the impala/r2d2 paths were exercised indirectly (via the bench
subprocess sweeps), so ``sebulba_muzero.py`` and ``quickstart.py`` could
rot silently — and the muzero example's documented 8-device invocation in
fact did (its fixed actor batch didn't divide across 6 learners).

Two layers:

  * fast tier — import every examples/*.py module (catches renamed
    imports, moved helpers, syntax rot at collection speed);
  * slow tier — run each RL entry point end to end for a few hundred
    frames in a 2-placeholder-device subprocess (real actor/learner core
    split, real fit loop, real result dict).
"""

import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

_EXAMPLES = pathlib.Path(__file__).resolve().parent.parent / "examples"
_ALL = sorted(p.stem for p in _EXAMPLES.glob("*.py"))

# every RL entry point + the flags that shrink it to smoke scale; keys are
# run labels (an example may appear more than once, e.g. with and without
# chaos injection), values are (example stem, argv)
_RL_RUNS = {
    "quickstart": ("quickstart", ["--frames", "2000"]),
    "sebulba_impala": ("sebulba_impala",
                       ["--frames", "400", "--actor-batch", "6",
                        "--trajectory", "5"]),
    "sebulba_impala_chaos": ("sebulba_impala",
                             ["--frames", "400", "--actor-batch", "6",
                              "--trajectory", "5", "--chaos", "7"]),
    "sebulba_impala_elastic_chaos": ("sebulba_impala",
                                     ["--frames", "400", "--actor-batch",
                                      "6", "--trajectory", "5", "--hosts",
                                      "3", "--chaos", "7"]),
    "sebulba_r2d2": ("sebulba_r2d2",
                     ["--frames", "400", "--actor-batch", "6",
                      "--trajectory", "6", "--burn-in", "1",
                      "--capacity", "64", "--replay-batch", "6",
                      "--min-size", "12", "--rnn-width", "16"]),
    "sebulba_muzero": ("sebulba_muzero",
                       ["--frames", "300", "--simulations", "4",
                        "--actor-batch", "6", "--trajectory", "6",
                        "--microbatches", "2"]),
    "sebulba_scenarios": ("sebulba_scenarios",
                          ["--frames", "400", "--actor-batch", "6",
                           "--trajectory", "5"]),
    "sebulba_scenarios_chaos": ("sebulba_scenarios",
                                ["--frames", "400", "--actor-batch", "6",
                                 "--trajectory", "5", "--chaos", "7"]),
    "train_lm_rl": ("train_lm_rl",
                    ["--preset", "tiny", "--frames", "256",
                     "--prompt-len", "4", "--actor-batch", "4"]),
    "train_lm_rl_replay": ("train_lm_rl",
                           ["--preset", "tiny", "--frames", "384",
                            "--prompt-len", "4", "--actor-batch", "4",
                            "--replay"]),
}


@pytest.mark.parametrize("name", _ALL)
def test_example_module_imports(name):
    spec = importlib.util.spec_from_file_location(
        f"examples_{name}", _EXAMPLES / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert hasattr(mod, "main"), name


@pytest.mark.slow
@pytest.mark.parametrize("label", sorted(_RL_RUNS))
def test_rl_example_runs_end_to_end(label, tmp_path):
    name, argv = _RL_RUNS[label]
    if name == "train_lm_rl":
        # the checkpoint goes to the test's own directory, never the repo
        argv = [*argv, "--ckpt", str(tmp_path / "train_lm_rl.npz")]
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [sys.executable, str(_EXAMPLES / f"{name}.py"), *argv],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FPS" in proc.stdout, proc.stdout[-2000:]
    if name == "train_lm_rl":
        assert (tmp_path / "train_lm_rl.npz").exists()
    if "--chaos" in argv:
        # the chaos run must survive its schedule and report supervision
        # counters (the example prints them only when --chaos is set)
        assert "chaos:" in proc.stdout, proc.stdout[-2000:]
    if "--hosts" in argv:
        # the elastic run must survive its host schedule and report the
        # membership counters (epoch / lost / joined / reshards)
        assert "hosts:" in proc.stdout, proc.stdout[-2000:]
