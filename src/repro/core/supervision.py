"""Actor supervision for Sebulba (ISSUE 7) — restart, quarantine, degrade.

The Podracer paper decouples actors from learners so the system survives
datacenter reality: preempted workers, stragglers, hung env processes.
This module owns that survival on a single host.  ``ActorSupervisor``
replaces Sebulba's bare thread list:

  * every actor *slot* (one per ``num_actor_cores x threads_per_actor_core``)
    is a supervised lifecycle, not a thread: crash -> restart with
    exponential backoff under a fresh RNG fold (the incarnation re-reads
    the versioned params slot on its first step, so a restarted actor acts
    on current policy, not the one it died under);
  * a slot that keeps dying is QUARANTINED after ``max_restarts`` restarts
    — the fleet degrades gracefully: every surviving actor produces full
    batches that shard across all learner cores, so training continues at
    reduced throughput rather than deadlocking or dying;
  * a heartbeat watchdog: each incarnation stamps a monotonic heartbeat
    every env step (and every blocked queue-put retry); a stamp older than
    ``stall_timeout`` means the actor is hung, not slow — the watchdog
    counts the stall, sets the incarnation's ``cancel`` event (cooperative
    faults and well-behaved envs unwind; a truly wedged thread is
    abandoned and reported at join), and the slot re-enters the restart /
    quarantine path;
  * when NO slot can make progress (all quarantined or stopped) the
    learner's queue drain raises :class:`SebulbaStallError` with a full
    diagnostics snapshot — per-slot states, heartbeat ages, restart
    counts, queue depth, param versions, and EVERY recorded traceback —
    instead of polling an empty queue forever or surfacing only the first
    crash.

State machine per slot::

    new -> running -> (clean exit) stopped
                   -> (crash / watchdog stall) --restarts < max--> restarting
                                               --else-----------> quarantined
    restarting --backoff elapsed--> running (fresh incarnation)

The supervisor is driven by the learner loop (``poll`` once per queue
drain iteration, <= ~0.5 s apart) — no extra monitor thread, no locks on
the actor hot path: an incarnation only writes its own ``ActorHandle``
fields (heartbeat stamp, counters), and the learner reads them.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Callable

# incarnation seeds fold the slot's base seed with a large prime so no two
# incarnations (or slots) ever reuse an env/RNG seed line
_SEED_STRIDE = 7919


class SebulbaStallError(RuntimeError):
    """The learner can no longer make progress: no live actor remains (or
    none has produced within the stall budget).  Carries a structured
    ``diagnostics`` snapshot — per-actor heartbeats and states, queue
    depth, param versions — and every per-thread traceback recorded over
    the run, so a cascading failure is diagnosed from all its symptoms,
    not the last one."""

    def __init__(self, message: str, diagnostics: dict):
        super().__init__(message)
        self.diagnostics = diagnostics


class ActorHandle:
    """One incarnation of one supervised actor slot.

    The actor loop runs against its handle: stamps ``beat()`` each step,
    accumulates its own counters (no cross-thread shared lists), and
    checks ``cancel`` so the watchdog can abandon it.  Aggregation sums
    over every handle the supervisor ever created — a restarted slot's
    frames are the sum of its incarnations' frames.
    """

    def __init__(self, slot: int, incarnation: int, core_id: int, seed: int,
                 injector=None):
        self.slot = slot
        self.incarnation = incarnation
        self.core_id = core_id
        self.seed = seed
        self.injector = injector  # persistent per-slot fault injector
        self.cancel = threading.Event()
        self.heartbeat = time.monotonic()
        self.frames = 0
        self.puts = 0  # trajectories handed to the learner
        self.put_blocked = 0
        self.traj_dropped = 0
        self.stats = None  # device-env FleetStats snapshot
        self.error: tuple[BaseException | None, str] | None = None
        self.first_put_at: float | None = None  # recovery-latency probe
        self.died_at: float | None = None
        self.thread: threading.Thread | None = None

    @property
    def name(self) -> str:
        return f"actor-{self.slot}r{self.incarnation}"

    def beat(self) -> None:
        self.heartbeat = time.monotonic()

    def mark_put(self) -> None:
        """Count a successful trajectory put and stamp the first one (and
        heartbeat).  The first-put stamp pairs with the previous
        incarnation's ``died_at`` to measure recovery latency."""
        self.heartbeat = time.monotonic()
        self.puts += 1
        if self.first_put_at is None:
            self.first_put_at = self.heartbeat

    def heartbeat_age(self, now: float | None = None) -> float:
        return (time.monotonic() if now is None else now) - self.heartbeat


class _Slot:
    def __init__(self, slot_id: int, core_id: int, base_seed: int, injector):
        self.slot_id = slot_id
        self.core_id = core_id
        self.base_seed = base_seed
        self.injector = injector
        self.state = "new"
        self.restarts = 0
        self.handles: list[ActorHandle] = []
        self.next_restart = 0.0

    @property
    def current(self) -> ActorHandle | None:
        return self.handles[-1] if self.handles else None


class ActorSupervisor:
    """Owns the actor fleet's threads and their lifecycle.

    ``spawn`` is the actor body — ``spawn(handle)`` runs the loop for one
    incarnation; the supervisor wraps it so every exception (including a
    scheduled fault) is recorded on the handle with its traceback instead
    of dying silently or masking later crashes.
    """

    def __init__(
        self,
        *,
        slots: list[tuple[int, int]],  # (core_id, base_seed) per slot
        spawn: Callable[[ActorHandle], None],
        stop: threading.Event,
        max_restarts: int = 3,
        restart_backoff: float = 0.05,
        stall_timeout: float = 60.0,
        fault_plan=None,
    ):
        if max_restarts < 0:
            raise ValueError("max_restarts must be >= 0")
        if restart_backoff <= 0:
            raise ValueError("restart_backoff must be > 0")
        if stall_timeout <= 0:
            raise ValueError("stall_timeout must be > 0")
        self._spawn = spawn
        self._stop = stop
        self.max_restarts = max_restarts
        self.restart_backoff = restart_backoff
        self.stall_timeout = stall_timeout
        self.actor_restarts = 0
        self.actor_quarantined = 0
        self.watchdog_stalls = 0
        self._slots = [
            _Slot(
                i, core_id, seed,
                fault_plan.actor_injector(i) if fault_plan is not None else None,
            )
            for i, (core_id, seed) in enumerate(slots)
        ]

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        now = time.monotonic()
        for slot in self._slots:
            self._spawn_incarnation(slot, now)

    def _spawn_incarnation(self, slot: _Slot, now: float) -> None:
        inc = len(slot.handles)
        handle = ActorHandle(
            slot.slot_id, inc, slot.core_id,
            seed=slot.base_seed + _SEED_STRIDE * inc,
            injector=slot.injector,
        )
        handle.heartbeat = now
        thread = threading.Thread(
            target=self._body, args=(handle,), daemon=True, name=handle.name
        )
        handle.thread = thread
        slot.handles.append(handle)
        slot.state = "running"
        thread.start()

    def _body(self, handle: ActorHandle) -> None:
        try:
            self._spawn(handle)
        except BaseException as e:  # record EVERY crash with its traceback
            handle.error = (e, traceback.format_exc())
        finally:
            handle.died_at = time.monotonic()

    # ----------------------------------------------------------- monitoring

    def poll(self, now: float | None = None) -> None:
        """Reap deaths, fire the watchdog, execute due restarts.  Driven by
        the learner loop every queue-drain iteration; all transitions are
        cheap host-side checks."""
        now = time.monotonic() if now is None else now
        for slot in self._slots:
            if slot.state == "running":
                handle = slot.current
                if not handle.thread.is_alive():
                    if handle.error is None:
                        # clean exit: shutdown or cooperative cancel
                        slot.state = "stopped"
                    else:
                        self._on_death(slot, now)
                elif (
                    handle.frames > 0
                    and handle.heartbeat_age(now) > self.stall_timeout
                ):
                    # hung, not slow: no heartbeat for a full stall budget.
                    # Incarnations that have not completed a step yet are
                    # exempt (startup grace): the first step jit-compiles
                    # the fused act/step program, which can dwarf any
                    # reasonable stall budget and is progress, not a hang.
                    # Cancel the incarnation (cooperative hangs unwind; a
                    # wedged thread is abandoned and reported at join) and
                    # put the slot through the restart/quarantine path.
                    self.watchdog_stalls += 1
                    handle.cancel.set()
                    if handle.error is None:
                        handle.error = (None, (
                            f"watchdog: {handle.name} heartbeat stalled "
                            f"({handle.heartbeat_age(now):.1f}s > "
                            f"{self.stall_timeout:.1f}s stall_timeout); "
                            "incarnation cancelled\n"
                        ))
                    handle.died_at = now
                    self._on_death(slot, now)
            if (
                slot.state == "restarting"
                and now >= slot.next_restart
                and not self._stop.is_set()
            ):
                slot.restarts += 1
                self.actor_restarts += 1
                self._spawn_incarnation(slot, now)

    def _on_death(self, slot: _Slot, now: float) -> None:
        if slot.restarts >= self.max_restarts:
            slot.state = "quarantined"
            self.actor_quarantined += 1
        else:
            slot.state = "restarting"
            slot.next_restart = now + self.restart_backoff * (2 ** slot.restarts)

    def can_progress(self, now: float | None = None) -> bool:
        """True while some slot can still feed the learner: running with a
        live heartbeat, pending restart, or not yet started.  False means
        the queue will never fill again — the learner must raise, not
        poll."""
        now = time.monotonic() if now is None else now
        for slot in self._slots:
            if slot.state in ("new", "restarting"):
                return True
            if slot.state == "running":
                handle = slot.current
                if handle.thread.is_alive() and (
                    handle.frames == 0  # startup grace: still compiling
                    or handle.heartbeat_age(now) <= self.stall_timeout
                ):
                    return True
        return False

    # ------------------------------------------------------------ reporting

    def handles(self) -> list[ActorHandle]:
        """Every incarnation ever spawned (counter aggregation surface)."""
        return [h for slot in self._slots for h in slot.handles]

    def errors(self) -> list[tuple[str, str]]:
        """(incarnation name, traceback) for every recorded failure, in
        slot/incarnation order — nothing is masked by arrival order."""
        return [
            (h.name, h.error[1])
            for slot in self._slots
            for h in slot.handles
            if h.error is not None
        ]

    def recovery_latencies(self) -> list[float]:
        """Seconds from each incarnation's death to its replacement's
        first successful trajectory put (the fleet's measured recovery
        latency).

        Incomplete pairs are DROPPED, never mis-paired: a dead
        incarnation with no replacement (quarantined slot) measures
        nothing, and an incarnation that died before its own first put
        (e.g. a replacement cancelled by the watchdog mid-compile)
        neither completes the previous pairing nor baselines the next —
        a latency is only ever adjacent death -> adjacent first put.
        """
        out = []
        for slot in self._slots:
            for prev, nxt in zip(slot.handles, slot.handles[1:]):
                if (
                    prev.first_put_at is not None
                    and prev.died_at is not None
                    and nxt.first_put_at is not None
                ):
                    out.append(max(0.0, nxt.first_put_at - prev.died_at))
        return out

    def diagnostics(self, now: float | None = None, **extra) -> dict:
        now = time.monotonic() if now is None else now
        actors = []
        for slot in self._slots:
            handle = slot.current
            actors.append({
                "slot": slot.slot_id,
                "core": slot.core_id,
                "state": slot.state,
                "restarts": slot.restarts,
                "incarnations": len(slot.handles),
                "heartbeat_age": (
                    round(handle.heartbeat_age(now), 3) if handle else None
                ),
                "alive": bool(handle and handle.thread.is_alive()),
                "frames": sum(h.frames for h in slot.handles),
                "last_error": (
                    repr(handle.error[0]) if handle and handle.error else None
                ),
            })
        return {
            "actors": actors,
            "actor_restarts": self.actor_restarts,
            "actor_quarantined": self.actor_quarantined,
            "watchdog_stalls": self.watchdog_stalls,
            **extra,
        }

    def stall_error(self, **extra) -> SebulbaStallError:
        """Build the structured learner-side stall error: diagnostics
        snapshot plus every recorded traceback."""
        diag = self.diagnostics(**extra)
        tracebacks = self.errors()
        lines = [
            "Sebulba learner stalled: no actor can make progress "
            f"({sum(1 for a in diag['actors'] if a['state'] == 'quarantined')}"
            f"/{len(diag['actors'])} quarantined).",
            f"diagnostics: {diag}",
        ]
        for name, tb in tracebacks:
            lines.append(f"--- {name} ---\n{tb.rstrip()}")
        diag["tracebacks"] = tracebacks
        return SebulbaStallError("\n".join(lines), diag)

    # ------------------------------------------------------------- shutdown

    def join(self, timeout: float) -> list[str]:
        """Join every incarnation ever spawned (current AND abandoned),
        spreading ``timeout`` across them; returns the names of threads
        that failed to stop (leaked — e.g. truly wedged in a hung env)."""
        threads = [
            h for h in self.handles()
            if h.thread is not None and h.thread.is_alive()
        ]
        deadline = time.monotonic() + timeout
        leaked = []
        for h in threads:
            h.cancel.set()
            h.thread.join(timeout=max(0.0, deadline - time.monotonic()))
            if h.thread.is_alive():
                leaked.append(h.name)
        return leaked
