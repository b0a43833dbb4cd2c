"""Every benchmarks/*.py module must import cleanly (fast tier).

The bench suites are invoked lazily (``benchmarks/run.py --suite ...``), so
a broken import — a renamed Sebulba internal, a moved helper — would
otherwise surface only when someone runs the benches.  Importing them all
here makes suite regressions fail test collection instead.
"""

import importlib
import pathlib

import pytest

_BENCH_DIR = pathlib.Path(__file__).resolve().parent.parent / "benchmarks"
_MODULES = sorted(
    p.stem for p in _BENCH_DIR.glob("*.py") if not p.stem.startswith("_")
) + ["_timing"]


@pytest.mark.parametrize("name", _MODULES)
def test_benchmark_module_imports(name):
    mod = importlib.import_module(f"benchmarks.{name}")
    assert hasattr(mod, "main") or name == "_timing", name


def test_run_registers_envs_suite():
    """``--suite envs`` stays wired to env_bench -> BENCH_envs.json."""
    import inspect

    from benchmarks import run

    assert '"envs": _envs_suite' in inspect.getsource(run.main)
    assert "BENCH_envs.json" in inspect.getsource(run._envs_suite)


def test_run_registers_fault_suite():
    """``--suite fault`` stays wired to fault_bench -> BENCH_fault.json
    (the ISSUE 7 supervision-degradation / recovery-latency suite)."""
    import inspect

    from benchmarks import run

    assert '"fault": _fault_suite' in inspect.getsource(run.main)
    assert "BENCH_fault.json" in inspect.getsource(run._fault_suite)


def test_run_registers_elastic_suite():
    """``--suite elastic`` stays wired to elastic_bench ->
    BENCH_elastic.json (the ISSUE 8 multi-host scale-out / host-kill
    recovery suite)."""
    import inspect

    from benchmarks import run

    assert '"elastic": _elastic_suite' in inspect.getsource(run.main)
    assert "BENCH_elastic.json" in inspect.getsource(run._elastic_suite)


def test_run_registers_lm_suite():
    """``--suite lm`` stays wired to lm_bench -> BENCH_lm.json (the ISSUE
    9 fused decode-carry vs full-forward re-scoring suite)."""
    import inspect

    from benchmarks import run

    assert '"lm": _lm_suite' in inspect.getsource(run.main)
    assert "BENCH_lm.json" in inspect.getsource(run._lm_suite)


def test_run_registers_serve_suite():
    """``--suite serve`` stays wired to serve_bench -> BENCH_serve.json
    (the ISSUE 10 continuous-vs-static batching suite)."""
    import inspect

    from benchmarks import run

    assert '"serve": _serve_suite' in inspect.getsource(run.main)
    assert "BENCH_serve.json" in inspect.getsource(run._serve_suite)


def test_run_fails_after_a_section_raises(capsys):
    """A section that raises does not stop the others, and the run then
    exits non-zero naming it: no failure is turned into success."""
    from benchmarks import run

    lines: list[str] = []
    run._section("fine", lambda: ["fine,1.0,x"], lines)
    run._section("broken", lambda: 1 / 0, lines)
    run._section("after", lambda: ["after,2.0,y"], lines)
    with pytest.raises(SystemExit) as exit_info:
        run._finish(lines)
    assert "broken" in str(exit_info.value.code)
    assert "fine" not in str(exit_info.value.code)
    assert "after,2.0,y" in capsys.readouterr().out
