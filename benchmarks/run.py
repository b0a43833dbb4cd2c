"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

Sections:
  * kernel micro-benches (the TPU-kernel oracle paths, timed on CPU)
  * Fig. 4a  — Anakin FPS vs device count   (anakin_scaling)
  * Fig. 4b  — Sebulba FPS vs actor batch   (sebulba_batch)
  * Fig. 4c  — MuZero FPS vs device count   (muzero_scaling)
  * §Anakin  — grid-world steps/sec single-device (the "5M steps/s on 8
    TPU cores" claim, CPU-scaled)
  * suites   — replay / sebulba (actor pipeline) / learner (donated
    update + publish throttling) / recurrent (R2D2 temporal core +
    burn-in), each writing its BENCH_*.json (schema documented in each
    suite module's docstring, honest-timing rules included)
  * roofline — aggregated dry-run table, if experiments/dryrun exists

``python -m benchmarks.run --quick`` runs only the fast sections (used by
CI); the full run takes ~10 minutes on a CPU host.  A section that raises
prints its traceback and a ``nan`` line, the remaining sections still run,
and the run then exits non-zero naming the failed sections.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


_ERROR = ",nan,error="  # marks the summary line of a section that raised


def _section(name: str, fn, lines: list[str]) -> None:
    print(f"# --- {name} ---", flush=True)
    try:
        out = fn()
        if out:
            lines.extend(out)
    except Exception as e:  # noqa: BLE001
        traceback.print_exc()
        lines.append(f"{name}{_ERROR}{type(e).__name__}")


def _finish(lines: list[str]) -> None:
    print("# --- summary CSV ---")
    for line in lines:
        print(line)
    failed = [line.split(_ERROR)[0] for line in lines if _ERROR in line]
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


def _anakin_single_device() -> list[str]:
    import jax

    from repro import optim
    from repro.agents.actor_critic import MLPActorCritic
    from repro.core.anakin import Anakin, AnakinConfig
    from repro.envs import Catch

    env = Catch()
    net = MLPActorCritic(env.num_actions, (64, 64))
    ank = Anakin(
        env, net, optim.adam(3e-3, clip_norm=1.0),
        AnakinConfig(unroll_length=10, batch_per_device=64,
                     iterations_per_call=50),
    )
    state = ank.init_state(jax.random.key(0))
    state, _ = ank.run(state)  # compile
    jax.block_until_ready(state)
    t0 = time.time()
    for _ in range(3):
        state, _ = ank.run(state)
    jax.block_until_ready(state)
    fps = 3 * ank.steps_per_call / (time.time() - t0)
    return [
        f"anakin_catch_1dev,{1e6 / fps:.3f},steps_per_s={fps:,.0f} "
        f"(paper: 5M steps/s on free 8-core TPU)"
    ]


def _replay_suite(lines: list[str]) -> None:
    """--suite replay: insert/sample throughput -> BENCH_replay.json (the
    perf trajectory future replay PRs regress against)."""
    from benchmarks import replay_bench

    _section(
        "replay insert/sample throughput",
        lambda: replay_bench.main(json_path="BENCH_replay.json"),
        lines,
    )


def _sebulba_suite(lines: list[str], include_e2e: bool = True) -> None:
    """--suite sebulba: fused-vs-legacy actor-loop numbers plus the
    subprocess end-to-end FPS -> BENCH_sebulba.json (the actor-pipeline
    perf trajectory)."""
    from benchmarks import sebulba_pipeline

    _section(
        "sebulba actor pipeline (fused vs legacy)",
        lambda: sebulba_pipeline.main(
            json_path="BENCH_sebulba.json", include_e2e=include_e2e
        ),
        lines,
    )


def _learner_suite(lines: list[str]) -> None:
    """--suite learner: donated/cached learner-update latency + publish
    transfer counts -> BENCH_learner.json (the learner-pipeline perf
    trajectory)."""
    from benchmarks import learner_bench

    _section(
        "sebulba learner pipeline (donated vs legacy)",
        lambda: learner_bench.main(json_path="BENCH_learner.json"),
        lines,
    )


def _envs_suite(lines: list[str]) -> None:
    """--suite envs: host BatchedHostEnv loop vs fused device fleet step
    at B=4/32 -> BENCH_envs.json (the env-pipeline perf trajectory)."""
    from benchmarks import env_bench

    _section(
        "env stepping (host pool vs device fleet)",
        lambda: env_bench.main(json_path="BENCH_envs.json"),
        lines,
    )


def _recurrent_suite(lines: list[str]) -> None:
    """--suite recurrent: R2D2 learner step — rglru-kernel vs lax-scan
    temporal core, burn-in 0 vs K overhead -> BENCH_recurrent.json (the
    recurrent-agent perf trajectory)."""
    from benchmarks import recurrent_bench

    _section(
        "recurrent learner (rglru vs lax core, burn-in overhead)",
        lambda: recurrent_bench.main(json_path="BENCH_recurrent.json"),
        lines,
    )


def _fault_suite(lines: list[str]) -> None:
    """--suite fault: supervised-Sebulba throughput-degradation curve
    (no-fault / crash-restart / hang-watchdog / quarantine) + measured
    recovery latency -> BENCH_fault.json (the fault-tolerance perf
    trajectory)."""
    from benchmarks import fault_bench

    _section(
        "fault suite (supervision degradation + recovery)",
        lambda: fault_bench.main(json_path="BENCH_fault.json"),
        lines,
    )


def _elastic_suite(lines: list[str]) -> None:
    """--suite elastic: multi-host membership scale-out (per-host fps
    flat 1->2->4) + SIGKILL host-loss recovery latency ->
    BENCH_elastic.json (the elasticity perf trajectory)."""
    from benchmarks import elastic_bench

    _section(
        "elastic suite (membership scale-out + host-kill recovery)",
        lambda: elastic_bench.main(json_path="BENCH_elastic.json"),
        lines,
    )


def _lm_suite(lines: list[str]) -> None:
    """--suite lm: actor decode throughput, fused KV-cache carry vs naive
    full-forward re-scoring at B=4/32 -> BENCH_lm.json (the LM-policy perf
    trajectory; acceptance floor >= 2x fused at B=32)."""
    from benchmarks import lm_bench

    _section(
        "lm decode (fused KV-cache carry vs full-forward re-scoring)",
        lambda: lm_bench.main(json_path="BENCH_lm.json"),
        lines,
    )


def _serve_suite(lines: list[str]) -> None:
    """--suite serve: continuous batching (paged KV + chunked prefill)
    vs static batching at mixed prompt/gen lengths -> BENCH_serve.json
    (the serving perf trajectory; acceptance floor >= 1.5x useful
    tokens/s over static on the mixed workload)."""
    from benchmarks import serve_bench

    _section(
        "serve (continuous vs static batching, mixed lengths)",
        lambda: serve_bench.main(json_path="BENCH_serve.json"),
        lines,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fast sections only")
    ap.add_argument("--suite",
                    choices=["all", "replay", "sebulba", "learner",
                             "recurrent", "envs", "fault", "elastic", "lm",
                             "serve"],
                    default="all",
                    help="'replay' -> BENCH_replay.json only; 'sebulba' -> "
                         "BENCH_sebulba.json only (actor pipeline + e2e FPS); "
                         "'learner' -> BENCH_learner.json only (donated "
                         "learner update + publish throttling); 'recurrent' "
                         "-> BENCH_recurrent.json only (R2D2 core + burn-in); "
                         "'envs' -> BENCH_envs.json only (host pool vs "
                         "device fleet stepping); 'fault' -> BENCH_fault.json "
                         "only (supervision degradation + recovery latency); "
                         "'elastic' -> BENCH_elastic.json only (multi-host "
                         "scale-out + host-kill recovery); 'lm' -> "
                         "BENCH_lm.json only (fused decode-carry acting vs "
                         "full-forward re-scoring); 'serve' -> "
                         "BENCH_serve.json only (continuous vs static "
                         "batching at mixed prompt/gen lengths)")
    args = ap.parse_args()

    lines: list[str] = []
    print("name,us_per_call,derived")

    suites = {
        "replay": _replay_suite,
        "sebulba": _sebulba_suite,
        "learner": _learner_suite,
        "recurrent": _recurrent_suite,
        "envs": _envs_suite,
        "fault": _fault_suite,
        "elastic": _elastic_suite,
        "lm": _lm_suite,
        "serve": _serve_suite,
    }
    if args.suite in suites:
        suites[args.suite](lines)
        _finish(lines)
        return

    from benchmarks import kernel_bench

    _section("kernels", kernel_bench.main, lines)
    _section("anakin single-device (paper §Anakin)", _anakin_single_device,
             lines)

    if not args.quick:
        from benchmarks import anakin_scaling, muzero_scaling, sebulba_batch

        _section("Fig 4a anakin scaling",
                 lambda: anakin_scaling.main((1, 2, 4, 8)), lines)
        _section("Fig 4b sebulba actor batch",
                 lambda: sebulba_batch.main((12, 24, 48)), lines)
        _section("Fig 4c muzero scaling",
                 lambda: muzero_scaling.main((4, 8)), lines)
        # keep the regression JSONs fresh on full runs, not just per-suite
        _replay_suite(lines)
        _sebulba_suite(lines)
        _learner_suite(lines)
        _recurrent_suite(lines)
        _envs_suite(lines)
        _fault_suite(lines)
        _elastic_suite(lines)
        _lm_suite(lines)
        _serve_suite(lines)

    # roofline table from dry-run artifacts, if present
    import glob

    if glob.glob("experiments/dryrun/*.json"):
        from benchmarks import roofline_table

        _section("roofline (from dry-run artifacts)",
                 lambda: roofline_table.main() and None, lines)

    _finish(lines)


if __name__ == "__main__":
    main()
