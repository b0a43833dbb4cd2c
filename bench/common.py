"""Pieces every cell of the benchmark shares: finding a cell's files by
name, the device check, seeds, the compile cache, peak memory, the
compile counter and the result line.

Nothing here knows a configuration, a traffic mix or a metric: those are
files under ``bench/`` found by the names in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import threading
import time

T0 = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a file by path: configuration, metric and driver files are
    named after entries of ``BENCHMARK.json`` (dots and dashes included),
    so they are loaded by path and never imported by package name."""
    name = name or "bench_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(BENCH))
    )
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with every file it names, resolved."""

    def __init__(self, name: str, benchmark: dict | None = None,
                 overrides: dict | None = None):
        """``benchmark`` and ``overrides`` ({"cfg": {...}, "traffic":
        {...}, "spec": {...}}) let the tests describe a cell and shrink it;
        runs pass neither."""
        benchmark = benchmark or load_json(ROOT / "BENCHMARK.json")
        entries = {w["name"]: w for w in benchmark["workloads"]}
        if name not in entries:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{sorted(entries)}"
            )
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        spec = BENCH / "workloads" / f"{name}.json"
        self.spec = load_json(spec) if spec.exists() or not overrides else {}
        cfg_entry = {c["name"]: c for c in benchmark["configs"]}[
            self.entry["config"]
        ]
        self.cfg = load_json(ROOT / cfg_entry["file"])
        self.cfg_module = load_module(
            BENCH / "configs" / f"{self.entry['config']}.py"
        )
        self.traffic = load_json(
            BENCH / "traffic" / f"{self.entry['traffic']}.json"
        )
        for part, extra in (overrides or {}).items():
            getattr(self, part).update(extra)
        self.end_to_end = [
            m for m in benchmark["end_to_end"]
            if name in m.get("workloads", [name])
        ]
        self.per_layer = [
            m for m in benchmark["per_layer"]
            if name in m.get("workloads", [name])
        ]
        self.driver = load_module(
            BENCH / "drivers" / f"{self.spec['driver']}.py"
        )

    def metric_reader(self, metric: str):
        return load_module(BENCH / "metrics" / f"{metric}.py")


# ------------------------------------------------------------------ device


def require_devices(chips: int):
    """The chips the cell asks for, or exit non-zero naming what JAX
    found.  Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU; JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind}), no TPU"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"bench: the cell needs {chips} TPU chips; JAX found {len(devs)}"
        )
    return devs[:chips]


def device_record(devices) -> dict:
    d = devices[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_memory(devices),
    }


def peak_memory(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``, where the backend
    reports it."""
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def enable_compile_cache() -> None:
    """The program's persistent compile cache (``<checkout>/.jax_cache``,
    or ``JAX_COMPILATION_CACHE_DIR`` where that is set), with every
    program kept, so that only a cell's first run in a checkout
    compiles."""
    import jax

    from repro.compile_cache import enable_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def seed_key(seed: int, salt: int = 0):
    """A JAX key from a seed of any size up to 64 bits, plus a salt that
    names what the key is for."""
    import jax

    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, salt)


def seed_int(seed: int, salt: int) -> int:
    """A 31-bit integer seed derived from ``seed`` and ``salt``."""
    x = (seed * 0x9E3779B97F4A7C15 + salt * 0xBF58476D1CE4E5B9) % (1 << 64)
    x ^= x >> 29
    x = (x * 0x94D049BB133111EB) % (1 << 64)
    return int((x ^ (x >> 32)) & 0x7FFFFFFF)


class CompileCounter:
    """Counts backend compilations, by JAX's own monitoring events, so a
    run can show that none fell inside its measured window; logs every
    tracing, lowering, compiling or cache step that takes a second or
    more, and counts persistent-cache hits and misses, so that set-up can
    be read."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.times: list[float] = []
        self.cache = {"hits": 0, "misses": 0}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE:
            with self._lock:
                self.times.append(time.perf_counter())
        if duration >= 1.0:
            log(f"{event.rsplit('/', 1)[-1]}: {duration:.1f} s")

    def _on_event(self, event: str, **_) -> None:
        for k in self.cache:
            if event.endswith(f"/cache_{k}"):
                with self._lock:
                    self.cache[k] += 1

    def between(self, t0: float, t1: float) -> int:
        with self._lock:
            return sum(t0 <= t <= t1 for t in self.times)


# ------------------------------------------------------------------ output


def emit(result: dict, checks: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output,
    with the checks under a key of their own that comes last."""
    for name, c in checks.items():
        state = "ok" if c["ok"] else "FAILED"
        print(
            f"check {name}: {c['value']!r} limit {c['limit']!r} {state}",
            file=sys.stderr,
        )
    sys.stderr.flush()
    out = dict(result)
    out["checks"] = {
        name: {"value": c["value"], "limit": c["limit"]}
        for name, c in checks.items()
    }
    print(json.dumps(out, allow_nan=True), flush=True)


def log(msg: str) -> None:
    """A line on standard error, stamped with the seconds since start."""
    print(f"bench: [{time.perf_counter() - T0:8.2f} s] {msg}",
          file=sys.stderr, flush=True)
