"""Multi-host elasticity: scale-out overhead + host-kill recovery.

Measures what the ISSUE 8 membership/routing layer costs and how fast
the fleet recovers from losing a host, with REAL subprocess workers
sharing one lease directory (the multi-host detection path, not an
in-process simulation):

  * ``scaleout`` — per-host training throughput as the membership grows
    1 -> 2 -> 4 hosts.  One member trains (the tiny HostBandit Sebulba,
    same topology as fault_bench) while the other members are
    subprocess lease-renewers; every learner drain iteration pays the
    full elastic path (cluster poll, registry sync over N lease files,
    epoch tag checks).  Per-host fps should be FLAT within 20% —
    membership size must not tax the training loop.

    Honesty note: this container has ONE cpu, so co-training workers
    would measure cpu contention, not elasticity overhead.  Scaling the
    *membership* while one member trains isolates exactly the cost this
    PR added; on a real pod each host has its own cores and the same
    flatness claim applies to co-training hosts.

  * ``host_kill`` — a subprocess member is SIGKILLed mid-run (no
    goodbye: its lease must EXPIRE).  Reports the measured recovery
    latency (kill -> membership epoch bump, lower-bounded by the lease
    ttl) and the survivor's ``hosts_lost`` / ``reshards`` accounting.

``benchmarks/run.py --suite elastic`` writes ``BENCH_elastic.json``:

    {"scaleout": {"1": {"per_host_fps", "frames", "seconds", "epoch"},
                  "2": {...}, "4": {...},
                  "per_host_flatness": min/max per-host fps},
     "host_kill": {"recovery_latency_s", "lease_ttl_s",
                   "hosts_lost", "reshards", "fps"}}

Honest timing: each training worker runs its own untimed warmup fit
(fresh process, fresh XLA compile cache) before its timed fit, and the
members are up (leases live, membership synced) before timing starts —
the scale-out numbers time steady-state training, never compiles or
fleet bring-up.  The kill is wall-clock (the parent waits for the timed
fit to begin), but detection is by lease expiry, so the measured
latency is the real contract: ttl + one sync interval.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

from benchmarks._timing import csv_line

TOTAL_FRAMES = 16_000
LEASE_TTL = 0.5
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sebulba(cluster=None):
    import repro.optim as optim
    from repro.agents import BatchedMLPActorCritic
    from repro.core.sebulba import Sebulba, SebulbaConfig
    from repro.envs import BatchedHostEnv, HostBandit

    return Sebulba(
        env_factory=lambda seed: HostBandit(seed=seed),
        make_batched_env=lambda f, n: BatchedHostEnv(f, n),
        network=BatchedMLPActorCritic(4, hidden=(16,)),
        optimizer=optim.sgd(1e-3),
        config=SebulbaConfig(
            num_actor_cores=1, threads_per_actor_core=2,
            actor_batch_size=4, trajectory_length=2, queue_capacity=2,
            max_restarts=2, restart_backoff=0.01,
        ),
        cluster=cluster,
    )


# ------------------------------------------------------------- worker side


def _train_worker(args) -> None:
    """One training host: join the membership, warm up untimed, touch
    the start marker, run the timed fit, print one JSON result line."""
    import jax

    from repro.distributed import HostSupervisor

    _sebulba(None).fit(jax.random.key(0), total_frames=256)  # compile cache
    sup = HostSupervisor(args.registry, args.host_id, ttl=args.ttl)
    seb = _sebulba(cluster=sup)
    marker = os.path.join(args.registry, f"started_{args.host_id}")
    with open(marker, "w") as f:
        f.write(str(os.getpid()))
    t0 = time.perf_counter()
    res = seb.fit(jax.random.key(0), total_frames=args.frames)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "host_id": args.host_id,
        "frames": res["frames"],
        "seconds": round(dt, 3),
        "fps": round(res["frames"] / dt, 1),
        "hosts_lost": res["hosts_lost"],
        "hosts_joined": res["hosts_joined"],
        "reshards": res["reshards"],
        "epoch": res["epoch"],
        "stale_epoch_trajs": seb.stale_epoch_trajs,
    }), flush=True)


def _member_worker(args) -> None:
    """One membership-only host: announce and renew until killed."""
    from repro.distributed import HostRegistry

    registry = HostRegistry(args.registry, ttl=args.ttl)
    registry.announce(args.host_id)
    while True:  # killed by the parent (scaleout: TERM; kill test: KILL)
        time.sleep(args.ttl / 3.0)
        registry.renew(args.host_id)


# ------------------------------------------------------------- parent side


def _spawn(mode: str, registry: str, host_id: str, *, frames: int = 0,
           ttl: float = LEASE_TTL) -> subprocess.Popen:
    cmd = [
        sys.executable, "-m", "benchmarks.elastic_bench",
        "--worker", mode, "--registry", registry, "--host-id", host_id,
        "--ttl", str(ttl),
    ]
    if frames:
        cmd += ["--frames", str(frames)]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # CPU workers: the parent may hold the chip
    src = os.path.join(_REPO_ROOT, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p
    )
    return subprocess.Popen(
        cmd, cwd=_REPO_ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
    )


def _wait_for(predicate, timeout: float, what: str) -> None:
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.02)


def _wait_live(registry_dir: str, n: int, ttl: float) -> None:
    from repro.distributed import HostRegistry

    reg = HostRegistry(registry_dir, ttl=ttl)
    _wait_for(
        lambda: len(reg.live_hosts()) >= n, timeout=30.0,
        what=f"{n} live leases in {registry_dir}",
    )


def _read_result(proc: subprocess.Popen, timeout: float = 300.0) -> dict:
    out, _ = proc.communicate(timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"worker failed (rc={proc.returncode}): {out}")
    return json.loads(out.strip().splitlines()[-1])


def _scaleout(tmp: str, total_frames: int) -> dict:
    results: dict[str, dict] = {}
    for n in (1, 2, 4):
        registry = os.path.join(tmp, f"scale{n}")
        members = [
            _spawn("member", registry, f"member{i}")
            for i in range(n - 1)
        ]
        try:
            if members:
                _wait_live(registry, n - 1, LEASE_TTL)
            trainer = _spawn(
                "train", registry, "trainer", frames=total_frames
            )
            res = _read_result(trainer)
        finally:
            for m in members:
                m.terminate()
            for m in members:
                m.wait(timeout=10.0)
        results[str(n)] = {
            "per_host_fps": res["fps"],
            "frames": res["frames"],
            "seconds": res["seconds"],
            "epoch": res["epoch"],
        }
    fps = [r["per_host_fps"] for r in results.values()]
    results["per_host_flatness"] = round(min(fps) / max(fps), 3)
    return results


def _host_kill(tmp: str, total_frames: int) -> dict:
    from repro.distributed import HostRegistry

    registry = os.path.join(tmp, "kill")
    victim = _spawn("member", registry, "victim")
    _wait_live(registry, 1, LEASE_TTL)
    trainer = _spawn("train", registry, "survivor", frames=total_frames)
    marker = os.path.join(registry, "started_survivor")
    _wait_for(
        lambda: os.path.exists(marker), timeout=120.0,
        what="survivor's timed fit to start",
    )
    time.sleep(0.2)  # let the timed fit get into steady state
    victim.send_signal(signal.SIGKILL)  # no goodbye: the lease must expire
    t_kill = time.monotonic()
    reg = HostRegistry(registry, ttl=LEASE_TTL)
    # the parent is a legitimate sync participant: racing bumps converge
    # (registry semantics), so polling here never confuses the survivor
    _wait_for(
        lambda: "victim" not in reg.sync().hosts, timeout=30.0,
        what="the victim's lease to expire and the epoch to bump",
    )
    latency = time.monotonic() - t_kill
    victim.wait(timeout=10.0)
    res = _read_result(trainer)
    return {
        "recovery_latency_s": round(latency, 3),
        "lease_ttl_s": LEASE_TTL,
        "hosts_lost": res["hosts_lost"],
        "reshards": res["reshards"],
        "fps": res["fps"],
        "stale_epoch_trajs": res["stale_epoch_trajs"],
    }


def bench(total_frames: int = TOTAL_FRAMES) -> dict:
    import tempfile

    with tempfile.TemporaryDirectory(prefix="elastic_bench_") as tmp:
        return {
            "scaleout": _scaleout(tmp, total_frames),
            "host_kill": _host_kill(tmp, total_frames),
        }


def write_json(results: dict, path: str = "BENCH_elastic.json") -> None:
    with open(path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)


def main(total_frames: int = TOTAL_FRAMES,
         json_path: str | None = None) -> list[str]:
    results = bench(total_frames)
    if json_path:
        write_json(results, json_path)
    lines = []
    for n in ("1", "2", "4"):
        r = results["scaleout"][n]
        us_per_frame = 1e6 * r["seconds"] / max(1, r["frames"])
        lines.append(csv_line(
            f"elastic/scaleout_{n}host", us_per_frame,
            f"per_host_fps={r['per_host_fps']} "
            f"flatness={results['scaleout']['per_host_flatness']}",
        ))
    k = results["host_kill"]
    lines.append(csv_line(
        "elastic/host_kill", 1e6 * k["recovery_latency_s"],
        f"recovery_s={k['recovery_latency_s']} ttl_s={k['lease_ttl_s']} "
        f"hosts_lost={k['hosts_lost']} reshards={k['reshards']}",
    ))
    return lines


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", choices=["train", "member"],
                    help="internal: run as a subprocess worker")
    ap.add_argument("--registry", help="shared lease directory (worker)")
    ap.add_argument("--host-id", help="this worker's host id")
    ap.add_argument("--ttl", type=float, default=LEASE_TTL)
    ap.add_argument("--frames", type=int, default=TOTAL_FRAMES)
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_elastic.json")
    args = ap.parse_args()
    if args.worker == "train":
        _train_worker(args)
    elif args.worker == "member":
        _member_worker(args)
    else:
        print("name,us_per_call,derived")
        for line in main(
            total_frames=args.frames,
            json_path="BENCH_elastic.json" if args.json else None,
        ):
            print(line)
        if args.json:
            print("wrote BENCH_elastic.json")
