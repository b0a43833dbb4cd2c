"""The unified Podracer runner surface (`repro.api`).

Both Podracer architectures front the same training contract:

    runner.fit(rng, total_frames, *, log_every=0,
               checkpoint_dir=None, checkpoint_every=0,
               restore_from=None, auto_resume=False) -> result dict

and every training entry point — on-policy Sebulba, off-policy (replay)
Sebulba, Anakin — returns ONE documented result schema (``RESULT_KEYS``).
Counters a given architecture does not have (Anakin never publishes params
or queues trajectories) are reported as 0, never missing, so downstream
tooling reads one shape.

Result schema (``make_result`` fills the defaults and rejects unknown
keys):

    params             final parameters (device pytree)
    updates            learner/optimizer updates applied
    frames             env frames generated
    fps                frames / seconds
    seconds            wall-clock of the fit
    param_version      logical params version (Sebulba: publish version the
                       actors observe; Anakin: update count)
    publishes_sent     actor-core param transfers dispatched (Sebulba)
    publishes_skipped  overlap-aware publish skips (Sebulba)
    publishes_aliased  publishes to an actor core that is also a learner
                       core: the slot takes the update's output, no copy
                       (Sebulba)
    put_blocked        full-queue retry intervals on the actor side
    traj_dropped       trajectories dropped at shutdown
    replay_size        filled replay slots at exit (off-policy Sebulba)
    checkpoints_saved  checkpoints written by the runner
    actor_restarts     supervised actor incarnations respawned after a
                       crash or watchdog stall (Sebulba)
    actor_quarantined  actor slots retired after max_restarts failures
    watchdog_stalls    hung-actor detections (heartbeat older than
                       stall_timeout)
    checkpoint_fallbacks  damaged checkpoints skipped while restoring
                       (restore fell back to the newest VALID stamp)
    hosts_joined       hosts observed joining the membership after start
                       (multi-host elastic Sebulba; includes rejoins)
    hosts_lost         hosts whose lease expired or retired mid-run
    reshards           membership epoch bumps observed (each triggers the
                       deterministic replay reshard + forced republish)
    epoch              final membership epoch (0 when not multi-host)
    policy_lag_mean    mean over updates of the params versions published
                       between the one a trajectory's first step acted with
                       and the one its update starts from (Sebulba, where
                       with replay it is the lag of the trajectory each
                       update inserts; 0 for Anakin, on-policy by
                       construction)
    policy_lag_max     the largest such lag
    mean_return        mean episode return (NaN when untracked)
    metrics            drained learner metrics (means since last drain)
    scenarios          per-scenario counters when training on a device-env
                       scenario mix ({name: {weight, rows, episodes,
                       reward_sum, return_sum, mean_return, [replay_slots]}},
                       empty dict otherwise)

Checkpointing: the runner owns persistence so examples stop hand-rolling
it.  Every ``checkpoint_every`` updates (and once more at the end of a
fit) the runner writes a ``param_version``-stamped npz via
``repro.checkpoint``; ``restore_from`` accepts a checkpoint file or a
directory (the newest VALID stamp wins — damaged checkpoints are skipped
and counted as ``checkpoint_fallbacks``).  ``auto_resume=True`` makes
``fit`` scan ``checkpoint_dir`` itself, so a preempted run relaunches
from wherever it last persisted with no extra flags.  The save syncs
params to host, so it costs one device->host pull per boundary — like
metric drains, it never touches the steady-state donated update loop.
"""

from __future__ import annotations

import contextlib
import math
import os
import re
from typing import Any, Protocol, runtime_checkable

import jax
import numpy as np

from repro import checkpoint
from repro.checkpoint import CheckpointCorruptError

PyTree = Any

RESULT_KEYS = (
    "params",
    "updates",
    "frames",
    "fps",
    "seconds",
    "param_version",
    "publishes_sent",
    "publishes_skipped",
    "publishes_aliased",
    "put_blocked",
    "traj_dropped",
    "replay_size",
    "checkpoints_saved",
    "actor_restarts",
    "actor_quarantined",
    "watchdog_stalls",
    "checkpoint_fallbacks",
    "hosts_joined",
    "hosts_lost",
    "reshards",
    "epoch",
    "policy_lag_mean",
    "policy_lag_max",
    "mean_return",
    "metrics",
    "scenarios",
)

_COUNTER_DEFAULTS = {
    "param_version": 0,
    "publishes_sent": 0,
    "publishes_skipped": 0,
    "publishes_aliased": 0,
    "put_blocked": 0,
    "traj_dropped": 0,
    "replay_size": 0,
    "checkpoints_saved": 0,
    "actor_restarts": 0,
    "actor_quarantined": 0,
    "watchdog_stalls": 0,
    "checkpoint_fallbacks": 0,
    "hosts_joined": 0,
    "hosts_lost": 0,
    "reshards": 0,
    "epoch": 0,
    "policy_lag_max": 0,
}


@runtime_checkable
class Runner(Protocol):
    """Anything that trains an Agent to a frame budget — Sebulba, Anakin,
    and whatever the next Podracer is.  ``fit`` owns the whole loop:
    initialization (or ``restore_from``), training, periodic checkpoints,
    and the unified result dict."""

    def fit(
        self,
        rng: jax.Array,
        total_frames: int,
        *,
        log_every: int = 0,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 0,
        restore_from: str | None = None,
        auto_resume: bool = False,
    ) -> dict: ...


def make_result(
    *,
    params: PyTree,
    updates: int,
    frames: int,
    seconds: float,
    metrics: dict,
    mean_return: float = float("nan"),
    scenarios: dict | None = None,
    policy_lag_mean: float = 0.0,
    **counters: int,
) -> dict:
    """Assemble the unified runner result.  Unset counters default to 0;
    a counter outside the schema is a programming error and raises."""
    unknown = set(counters) - set(_COUNTER_DEFAULTS)
    if unknown:
        raise TypeError(f"unknown result counters: {sorted(unknown)}")
    out = {
        "params": params,
        "updates": int(updates),
        "frames": int(frames),
        "fps": float(frames) / seconds if seconds > 0 else 0.0,
        "seconds": float(seconds),
        "mean_return": float(mean_return),
        "policy_lag_mean": float(policy_lag_mean),
        "metrics": dict(metrics),
        "scenarios": dict(scenarios) if scenarios else {},
    }
    for key, default in _COUNTER_DEFAULTS.items():
        out[key] = int(counters.get(key, default))
    return out


# ------------------------------------------------------------- serve results

SERVE_RESULT_KEYS = (
    "outputs",
    "completed",
    "admitted",
    "preempted",
    "steps",
    "prefill_chunks",
    "tokens_prefilled",
    "tokens_decoded",
    "tokens_per_s",
    "seconds",
    "queue_depth_peak",
    "cache_occupancy_peak",
    "cache_occupancy_mean",
    "ttft_p50",
    "ttft_p95",
    "tpot_p50",
    "tpot_p95",
)

_SERVE_INT_DEFAULTS = {
    "completed": 0,
    "admitted": 0,
    "preempted": 0,
    "steps": 0,
    "prefill_chunks": 0,
    "tokens_prefilled": 0,
    "tokens_decoded": 0,
    "queue_depth_peak": 0,
}

_SERVE_FLOAT_DEFAULTS = {
    "cache_occupancy_peak": 0.0,
    "cache_occupancy_mean": 0.0,
    "ttft_p50": 0.0,
    "ttft_p95": 0.0,
    "tpot_p50": 0.0,
    "tpot_p95": 0.0,
}


def make_serve_result(
    *,
    outputs: dict,
    seconds: float,
    **counters,
) -> dict:
    """Assemble the unified ServeEngine result — the serving twin of
    ``make_result``: one documented schema (``SERVE_RESULT_KEYS``), unset
    counters default to 0 (absent-as-0, never missing), unknown counters
    raise.

        outputs               {request id: [generated token ids]}
        completed             requests finished
        admitted              queue -> row admissions (re-admissions after
                              a preemption count again)
        preempted             cache-pressure preemptions (recompute-on-
                              restart; outputs stay deterministic)
        steps                 engine iterations
        prefill_chunks        chunked-prefill dispatches
        tokens_prefilled      prompt tokens written through prefill
        tokens_decoded        decode-step tokens processed
        tokens_per_s          (tokens_prefilled + tokens_decoded) / seconds
        seconds               wall-clock of the run
        queue_depth_peak      max requests waiting in the queue
        cache_occupancy_peak  max fraction of KV pages (paged) or rows
                              (dense) in use
        cache_occupancy_mean  mean of the same, over steps
        ttft_p50 / ttft_p95   time-to-first-token percentiles (s)
        tpot_p50 / tpot_p95   time-per-output-token percentiles (s)
    """
    known = set(_SERVE_INT_DEFAULTS) | set(_SERVE_FLOAT_DEFAULTS)
    unknown = set(counters) - known
    if unknown:
        raise TypeError(f"unknown serve counters: {sorted(unknown)}")
    out = {"outputs": dict(outputs), "seconds": float(seconds)}
    for key, default in _SERVE_INT_DEFAULTS.items():
        out[key] = int(counters.get(key, default))
    for key, default in _SERVE_FLOAT_DEFAULTS.items():
        out[key] = float(counters.get(key, default))
    tokens = out["tokens_prefilled"] + out["tokens_decoded"]
    out["tokens_per_s"] = tokens / out["seconds"] if out["seconds"] > 0 else 0.0
    return out


# ------------------------------------------------------------ checkpoints

# \d+ (not \d{8}): the zero-padded stamp is min-width, so versions past
# 10^8 write 9+ digit names — they must stay visible to restore
_CKPT_RE = re.compile(r"^ckpt_(\d+)\.npz$")


def checkpoint_path(directory: str, param_version: int) -> str:
    return os.path.join(directory, f"ckpt_{param_version:08d}.npz")


def save_checkpoint(
    directory: str,
    params: PyTree,
    *,
    param_version: int,
    updates: int = 0,
    frames: int = 0,
    fault=None,
) -> str:
    """Write a ``param_version``-stamped checkpoint (atomic npz with an
    embedded checksum) and return its path.  The stamp names the file, so
    a directory of checkpoints sorts by version and ``latest_checkpoint``
    needs no sidecar index.  ``fault`` threads the deterministic
    checkpoint injector (repro.fault) into the writer."""
    path = checkpoint_path(directory, param_version)
    checkpoint.save(path, {"params": params, "meta": _meta(
        param_version=param_version, updates=updates, frames=frames
    )}, fault=fault)
    return path


def _meta(**values: int) -> dict:
    return {k: np.asarray(v, np.int64) for k, v in values.items()}


def checkpoint_stamps(directory: str) -> list[tuple[int, str]]:
    """Every ``ckpt_*.npz`` in ``directory`` as (version, path), newest
    first.  Compared numerically — lexical order breaks once stamps
    outgrow the 8-digit zero padding.  Non-checkpoint debris (e.g. the
    tmp files a killed write leaves behind) is ignored."""
    if not os.path.isdir(directory):
        return []
    stamps = []
    for name in os.listdir(directory):
        m = _CKPT_RE.match(name)
        if m:
            stamps.append((int(m.group(1)), os.path.join(directory, name)))
    return sorted(stamps, reverse=True)


def latest_checkpoint(directory: str) -> str | None:
    """Highest-``param_version`` checkpoint in ``directory`` (None if the
    directory is missing or holds no checkpoints)."""
    stamps = checkpoint_stamps(directory)
    return stamps[0][1] if stamps else None


def newest_valid_checkpoint(directory: str) -> str | None:
    """Path of the newest stamp that passes checksum verification, or
    None when no valid checkpoint exists.  The rejoining-host restore
    source (multi-host elasticity): a host re-entering the fleet resumes
    from here, skipping any stamp another host tore mid-preemption —
    same fallback order as ``restore_checkpoint`` on a directory, but
    read-only and without materializing params."""
    for _, path in checkpoint_stamps(directory):
        if checkpoint.verify(path):
            return path
    return None


def _restore_file(path: str, params_like: PyTree) -> tuple[PyTree, dict]:
    like = {
        "params": params_like,
        "meta": _meta(param_version=0, updates=0, frames=0),
    }
    tree = checkpoint.restore(path, like)
    meta = {k: int(v) for k, v in tree["meta"].items()}
    return tree["params"], meta


def restore_checkpoint(path: str, params_like: PyTree) -> tuple[PyTree, dict]:
    """Restore ``(params, meta)`` from a checkpoint file, or from the
    newest VALID checkpoint when ``path`` is a directory: damaged stamps
    (torn writes, corruption — ``CheckpointCorruptError``) are skipped
    newest-to-oldest and counted in ``meta["fallbacks"]``, so a
    checkpoint-write kill never strands a resumable run.  ``params_like``
    supplies the target structure (shapes validated by repro.checkpoint);
    ``meta`` holds the int stamps (param_version, updates, frames)."""
    if not os.path.isdir(path):
        params, meta = _restore_file(path, params_like)
        meta["fallbacks"] = 0
        return params, meta
    stamps = checkpoint_stamps(path)
    if not stamps:
        raise FileNotFoundError(f"no ckpt_*.npz checkpoints in {path}")
    skipped: list[str] = []
    for _, ckpt_path in stamps:
        try:
            params, meta = _restore_file(ckpt_path, params_like)
        except CheckpointCorruptError:
            skipped.append(ckpt_path)
            continue
        meta["fallbacks"] = len(skipped)
        return params, meta
    raise CheckpointCorruptError(
        f"every checkpoint in {path} is damaged: {skipped}"
    )


def restore_for_fit(
    restore_from: str, params_like: PyTree, opt, sharding
) -> tuple[PyTree, PyTree, dict]:
    """The shared runner warm-start: restore params from a checkpoint (or
    a directory's newest valid stamp), place them on ``sharding``, and
    build a FRESH optimizer state for them (research-checkpoint semantics
    — only params persist).  Returns ``(params, opt_state, meta)``; the
    caller continues its version line from ``meta`` so post-restore
    stamps sort above the restored one, and surfaces
    ``meta["fallbacks"]`` as the ``checkpoint_fallbacks`` counter."""
    restored, meta = restore_checkpoint(restore_from, params_like)
    params = jax.device_put(restored, sharding)
    opt_state = jax.device_put(opt.init(params), sharding)
    return params, opt_state, meta


def resolve_auto_resume(
    restore_from: str | None, checkpoint_dir: str | None, auto_resume: bool
) -> str | None:
    """The ``fit(..., auto_resume=True)`` contract, shared by runners:
    scan ``checkpoint_dir`` and resume from it when it holds any stamped
    checkpoint, start fresh when it does not (first launch).  Explicit
    ``restore_from`` and ``auto_resume`` are mutually exclusive — the
    caller must pick one recovery source."""
    if not auto_resume:
        return restore_from
    if restore_from is not None:
        raise ValueError(
            "auto_resume=True scans checkpoint_dir itself; drop "
            "restore_from (or pass it alone)"
        )
    if not checkpoint_dir:
        raise ValueError(
            "auto_resume=True needs checkpoint_dir: that is the directory "
            "a preempted run re-scans on relaunch"
        )
    return checkpoint_dir if checkpoint_stamps(checkpoint_dir) else None


class CheckpointPolicy:
    """Host-side boundary logic shared by the runners: save every
    ``every`` updates plus a final save, count what was written, and keep
    the donated update loop untouched in between.  Inert (zero branches
    taken) when ``directory`` is None or ``every`` is 0 — except that a
    bare ``directory`` still gets the final save, so ``fit(...,
    checkpoint_dir=...)`` alone persists the result."""

    def __init__(self, directory: str | None, every: int,
                 base_updates: int = 0, fault=None,
                 span: str | None = None):
        if every < 0:
            raise ValueError("checkpoint_every must be >= 0")
        if every and not directory:
            raise ValueError(
                "checkpoint_every requires checkpoint_dir: the runner "
                "needs somewhere to write the stamped checkpoints"
            )
        self.directory = directory
        self.every = every
        self.fault = fault  # checkpoint fault injector (repro.fault)
        self.span = span  # profiler span around each write, or None
        self.saved = 0
        self._last_version = None
        # seed the boundary from the restored update count, so a resumed
        # fit's first save lands at the NEXT boundary instead of
        # re-writing a near-duplicate of the just-restored params
        self._base_updates = base_updates
        self._last_boundary = base_updates // every if every else 0

    def _save(self, params, *, param_version: int, updates: int,
              frames: int) -> None:
        with (jax.profiler.TraceAnnotation(self.span) if self.span
              else contextlib.nullcontext()):
            save_checkpoint(
                self.directory, params, param_version=param_version,
                updates=updates, frames=frames, fault=self.fault,
            )
        self.saved += 1
        self._last_version = param_version

    def maybe_save(self, params, *, param_version: int, updates: int,
                   frames: int) -> None:
        """Call whenever the update count advances (by one — Sebulba — or
        by a compiled block — Anakin); saves once per crossed ``every``
        boundary.  Cheap int check unless it fires."""
        if not (self.directory and self.every):
            return
        boundary = updates // self.every
        if boundary > self._last_boundary:
            self._last_boundary = boundary
            self._save(params, param_version=param_version, updates=updates,
                       frames=frames)

    def final_save(self, params, *, param_version: int, updates: int,
                   frames: int) -> None:
        """End-of-fit save, skipped when the boundary save already caught
        this exact version — or when THIS fit trained nothing (``updates``
        is cumulative; a resumed fit that did zero new updates would
        otherwise re-write the just-restored params)."""
        if (
            self.directory
            and updates > self._base_updates
            and self._last_version != param_version
        ):
            self._save(params, param_version=param_version, updates=updates,
                       frames=frames)


def updates_for_frames(total_frames: int, frames_per_update: int) -> int:
    """Minimum updates covering ``total_frames`` (ceil division) — shared
    by runners that step in fixed frame chunks (Anakin)."""
    return max(1, math.ceil(total_frames / frames_per_update))
