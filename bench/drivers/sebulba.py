"""Sebulba training cells: build the program from a configuration and a
traffic mix, drive ``Sebulba.fit`` through set-up, a measured window and
an optional traced window, then check what the timed path produced
against the configuration's plain reference.

How the window is bounded without touching the program: ``fit`` runs in a
helper thread with a frame budget that stays unreached until the window
has closed (``_Budget``), and the learner's update is wrapped (``Probe``)
to count updates and to put one tiny program behind each of them, whose
completion marks the update's completion on the device.  The rate is the
frames the actors acted between two such marks over the time between them
(``window``).  It equals the frames trained plus the change in the backlog
(the frames acted but not yet trained), and the backlog is bounded by the
queue, the actors' rings and the trajectory the learner holds
(``max_backlog``): ``correct`` fails a run whose backlog at a mark lies
above that bound, so frames that the learner drops or skips cannot count.

What ``correct`` compares.  The same ``Sebulba`` object that set-up
drives through its first updates runs the window.  The probe keeps host
copies of what those first updates saw and made: the first three
trajectories, each update's loss (from the metrics accumulator), the
first gradient as the optimizer took it (from its state after one step)
and the parameters after three updates (as the fourth receives them).
Once the window has closed and the program's state is freed, the
configuration's float32 reference follows the same three updates from
the same seeded weights on the same trajectories, and four numbers are
compared, each with a limit from the cell's file:

  logp_gap    widest |behaviour log-prob - reference log-prob| over the
              first trajectory (acted on with the initial weights):
              the act step, its decode or torso and its sampling record;
  loss_gap    widest gap of the three updates' losses, in the loss's
              own units (a loss can lie near zero, so no relative gap);
  grad_gap    worst leaf's gap of first-gradient norms;
  change_gap  worst leaf's gap of the norms of the parameters' change
              after three updates, over leaves whose reference gradient
              is above a thousandth of the median leaf's.
"""

from __future__ import annotations

import functools
import gc
import math
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, devtrace
from bench import reference as ref

TRAJ_FIELDS = ("obs", "actions", "rewards", "discounts", "behaviour_logp",
               "bootstrap_obs")
CAPTURED = 3  # updates the reference follows


# ------------------------------------------------------------------ build


def make_optimizer(spec: dict):
    from repro import optim

    if spec["name"] == "adam":
        return optim.adam(spec["lr"], spec["b1"], spec["b2"], spec["eps"],
                          clip_norm=spec["clip_norm"])
    if spec["name"] == "rmsprop":
        return optim.rmsprop(spec["lr"], spec["decay"], spec["eps"],
                             clip_norm=spec["clip_norm"])
    raise ValueError(f"unknown optimizer {spec['name']!r}")


def _lm_agent(cfg: dict, traffic: dict):
    from repro.agents.lm_policy import LMPolicyAgent
    from repro.configs.base import ArchConfig
    from repro.envs import TokenEnv
    from repro.launch.steps import TrainHParams

    arch = ArchConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=cfg["qkv_bias"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
    )
    env = TokenEnv(vocab_size=arch.vocab_size,
                   prompt_len=traffic["prompt_len"],
                   data_vocab=traffic["data_vocab"])
    if traffic["trajectory_length"] != env.episode_len:
        raise ValueError("LM cells train on whole episodes: "
                         "trajectory_length must be 2 * prompt_len")
    hp = cfg["loss"]
    agent = LMPolicyAgent(arch, max_seq=env.episode_len, hparams=TrainHParams(
        rl_weight=hp["rl_weight"], entropy_cost=hp["entropy_cost"],
        value_cost=hp["value_cost"], aux_weight=hp["aux_weight"],
    ))
    return {"agent": agent, "device_env": env}, {}


def _impala_agent(cfg: dict, traffic: dict):
    from repro.agents.impala import ConvActorCritic
    from repro.envs import Pong

    env = Pong(height=cfg["frame_height"], width=cfg["frame_width"])
    if env.num_actions != cfg["num_actions"] or env.obs_shape[-1] != cfg["frame_channels"]:
        raise ValueError("the configuration's actions and frames do not match the env")
    net = ConvActorCritic(cfg["num_actions"], channels=tuple(cfg["channels"]),
                          blocks=cfg["blocks_per_stage"], hidden=cfg["hidden"])
    hp = cfg["loss"]
    return {"network": net, "device_env": env}, {
        "entropy_cost": hp["entropy_cost"], "value_cost": hp["value_cost"],
        "discount": hp["discount"],
    }


AGENTS = {"lm_policy": _lm_agent, "impala_conv": _impala_agent}


_JITS: dict = {}


def _jit_once(key, make):
    """One jitted function per key and process, so that a process that runs
    many seeds traces and loads each program once."""
    if key not in _JITS:
        _JITS[key] = make()
    return _JITS[key]


def make_params(cell, seed: int):
    """The seeded weights, made on the default device in one jitted call
    in the dtypes the configuration states."""
    fn = _jit_once((cell, "params"), lambda: jax.jit(
        functools.partial(cell.cfg_module.make_params, cell.cfg)))
    return fn(common.seed_key(seed, 1))


def build(cell, seed: int, devices):
    from repro.core.sebulba import Sebulba, SebulbaConfig

    cfg, traffic = cell.cfg, cell.traffic
    parts, loss_kw = AGENTS[cfg["agent"]](cfg, traffic)
    scfg = SebulbaConfig(
        num_actor_cores=traffic["num_actor_cores"],
        threads_per_actor_core=traffic["threads_per_actor_core"],
        actor_batch_size=traffic["actor_batch_size"],
        trajectory_length=traffic["trajectory_length"],
        queue_capacity=traffic["queue_capacity"],
        stall_timeout=cfg["stall_timeout_s"],
        **loss_kw,
    )
    seb = Sebulba(optimizer=make_optimizer(cfg["optimizer"]), config=scfg,
                  devices=list(devices), **parts)
    # the benchmark's weights and traffic, from the seed: the agent's init
    # returns the seeded weights, and every actor slot's env and sampling
    # stream derives from the seed
    params = make_params(cell, seed)
    seb.agent.init = lambda rng, obs_shape: params
    for i, slot in enumerate(seb.supervisor._slots):
        slot.base_seed = common.seed_int(seed, 100 + i)
    return seb


# ------------------------------------------------------------------ probe


class _Budget:
    """A frame budget that ``fit`` never reaches until ``stop`` is set:
    ``frames < budget`` asks ``budget.__gt__(frames)``."""

    def __init__(self):
        self.stop = False

    def __gt__(self, frames) -> bool:
        return not self.stop


_marker = jax.jit(lambda m: m[0])


def _generic_sumsq(tree) -> dict:
    """Per-leaf sums of squares, keyed by the leaf's path."""
    return {jax.tree_util.keystr(p): jnp.sum(jnp.square(x.astype(jnp.float32)))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


_sumsq = jax.jit(_generic_sumsq)
_sums = jax.jit(lambda tree: {
    jax.tree_util.keystr(p): jnp.sum(x.astype(jnp.float32))
    for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]})


def _floats(d: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(d).items()}


def grad_norms_from_state(opt_state, spec: dict) -> dict:
    """Per-leaf norms of the first gradient as the optimizer took it,
    from the optimizer's state after one step: Adam's first moment is
    (1 - b1) g, RMSProp's second moment (1 - decay) g^2."""
    for s in opt_state:
        if spec["name"] == "adam" and hasattr(s, "mu"):
            sq = _floats(_sumsq(s.mu))
            return {k: math.sqrt(v) / (1 - spec["b1"]) for k, v in sq.items()}
        if spec["name"] == "rmsprop" and hasattr(s, "nu"):
            tot = _floats(_sums(s.nu))
            return {k: math.sqrt(max(v, 0.0) / (1 - spec["decay"]))
                    for k, v in tot.items()}
    raise ValueError("optimizer state has no moment to read the gradient from")


class Mark(NamedTuple):
    updates: int  # learner updates completed on the device
    t: float  # the host clock then
    acted: int  # frames the actors had acted by then (``Sebulba.frames``)


def window(m0: Mark, m1: Mark, frames_per_update: int) -> dict:
    """The rate between two marks: the frames acted between them over the
    time between them.  That is the frames trained plus the change in the
    backlog, the frames acted but not yet trained (``acted - updates x
    frames per update``): a queue that fills or drains inside the window
    moves the trained count by whole trajectories and this rate by nothing.
    While the backlog stays within ``max_backlog`` the two rates differ by
    less than that bound over the window.  The trained count and both
    backlogs are kept to check the rate by."""
    b0 = m0.acted - m0.updates * frames_per_update
    b1 = m1.acted - m1.updates * frames_per_update
    return {"k0": m0.updates, "k1": m1.updates, "t0": m0.t, "t1": m1.t,
            "trained_frames": (m1.updates - m0.updates) * frames_per_update,
            "backlog_frames": [b0, b1],
            "train_frames_per_s": (m1.acted - m0.acted) / (m1.t - m0.t)}


def max_backlog(traffic: dict) -> int:
    """The most frames a sound program can have acted and not yet trained:
    a full queue, one ring on each actor thread (filling, or full and
    waiting to be put) and the trajectory the learner has taken from the
    queue and not yet updated on."""
    threads = traffic["num_actor_cores"] * traffic["threads_per_actor_core"]
    trajs = traffic["queue_capacity"] + threads + 1
    return trajs * traffic["actor_batch_size"] * traffic["trajectory_length"]


class Probe:
    """Wraps the learner's donated update: counts updates, marks each
    one's completion with a tiny program on its output, and keeps host
    copies of what the first updates saw and made (taken before the
    donation hands the buffers on)."""

    def __init__(self, seb, opt_spec: dict):
        self.opt_spec = opt_spec
        self.macc_spec = None
        self.count = 0
        self.marker = None
        self.cond = threading.Condition()
        self.trajs: list[dict] = []
        self.maccs: list[np.ndarray] = []
        self.g1: dict | None = None
        self.p3 = None
        orig = seb._get_update

        def get_update(traj):
            update, core = orig(traj)
            return self._wrap(update, seb), core

        seb._get_update = get_update

    def _wrap(self, update, seb):
        def run(params, opt_state, traj, macc):
            k = self.count + 1
            if k <= CAPTURED + 1:
                self.macc_spec = seb._macc_spec
                self._capture(k, params, opt_state, traj, macc)
            out = update(params, opt_state, traj, macc)
            marker = _marker(out[2])
            with self.cond:
                self.count, self.marker = k, marker
                self.cond.notify_all()
            return out

        return run

    def _capture(self, k, params, opt_state, traj, macc):
        common.log(f"update {k}: dispatching, host copies of its inputs")
        if k <= CAPTURED:
            self.trajs.append({
                f: np.asarray(jax.device_get(getattr(traj, f)))
                for f in TRAJ_FIELDS
            })
        if k >= 2:
            self.maccs.append(np.asarray(jax.device_get(macc)))
        if k == 2:
            self.g1 = grad_norms_from_state(opt_state, self.opt_spec)
        if k == CAPTURED + 1:
            self.p3 = jax.device_get(params)

    def wait_for(self, k: int, alive, timeout: float = 1200.0) -> None:
        deadline = time.monotonic() + timeout
        with self.cond:
            while self.count < k:
                if not alive():
                    raise RuntimeError("fit ended before the window")
                if time.monotonic() > deadline:
                    raise RuntimeError(f"no update {k} in {timeout} s")
                self.cond.wait(0.5)

    def mark(self) -> tuple[int, float]:
        """Wait for the newest dispatched update to complete on the device
        -> (its index, the host clock then)."""
        with self.cond:
            k, m = self.count, self.marker
        m.block_until_ready()
        return k, time.perf_counter()

    def program_losses(self) -> list[float]:
        """Each captured update's loss, from the running metric sums."""
        spec = self.macc_spec
        idx = 1 + jax.tree.unflatten(spec, list(range(spec.num_leaves)))["loss"]
        sums = [0.0] + [float(m[idx]) for m in self.maccs]
        counts = [float(m[0]) for m in self.maccs]
        if counts != [float(i) for i in range(1, CAPTURED + 1)]:
            raise RuntimeError(f"metric accumulator counts {counts}")
        return [b - a for a, b in zip(sums, sums[1:])]


class Session:
    """A built program running ``fit`` in a helper thread."""

    def __init__(self, cell, seed: int, devices):
        self.cell = cell
        self.seb = build(cell, seed, devices)
        common.log("program built, seeded weights made")
        self.probe = Probe(self.seb, cell.cfg["optimizer"])
        self.budget = _Budget()
        self.result: dict | None = None
        self.error: BaseException | None = None
        self.thread = threading.Thread(target=self._fit, name="bench-fit",
                                       daemon=True)
        self.thread.start()

    def _fit(self):
        try:
            self.result = self.seb.fit(jax.random.key(0), self.budget)
        except BaseException as e:  # reported by stop()
            self.error = e
            with self.probe.cond:
                self.probe.cond.notify_all()

    def alive(self) -> bool:
        return self.thread.is_alive()

    def mark(self) -> Mark:
        """The probe's mark and the frames the actors had acted by then.
        (The probe outlives the program for the reference, so it keeps no
        hold on the program.)"""
        k, t = self.probe.mark()
        return Mark(k, t, self.seb.frames)

    def measure(self, seconds: float) -> dict:
        """Frames acted per second between two completed updates about
        ``seconds`` apart (``window``)."""
        m0 = self.mark()
        while time.perf_counter() - m0.t < seconds:
            if not self.alive():
                raise RuntimeError("fit ended inside the window")
            time.sleep(0.05)
        m1 = self.mark()
        tr = self.cell.traffic
        return window(m0, m1, tr["actor_batch_size"] * tr["trajectory_length"])

    def stop(self) -> dict:
        self.budget.stop = True
        self.thread.join(timeout=120.0)
        if self.thread.is_alive():
            raise RuntimeError("fit did not stop within 120 s")
        if self.error is not None:
            raise self.error
        return self.result


# ------------------------------------------------------------- reference


def _to_device(traj: dict) -> dict:
    return {k: jnp.asarray(v) for k, v in traj.items()}


def _rows(traj: dict, rows) -> dict:
    return traj if rows is None else {k: v[rows] for k, v in traj.items()}


class Follower:
    """The reference put through the same updates as the program: from the
    seeded weights, over the captured trajectories, in the configuration's
    stated storage dtypes and float32 arithmetic.  The reference keeps the
    weights in its own layout (the configuration's ``to_reference``) and
    reports per-leaf norms under the program's leaf names (its
    ``leaf_sumsq``)."""

    def __init__(self, cell, num: ref.Numerics = ref.HIGHEST,
                 loss_rows=None, grad_rows=None):
        cfg, mod = cell.cfg, cell.cfg_module
        self.spec = cfg["optimizer"]
        to_ref = getattr(mod, "to_reference", lambda p: p)
        sumsq = getattr(mod, "leaf_sumsq", _generic_sumsq)

        def loss_fn(p32, traj):
            return mod.loss(p32, traj, cfg, num)

        def step(p, traj):
            p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
            with jax.default_matmul_precision("highest"):
                (l, logp), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    p32, _rows(traj, grad_rows))
                if loss_rows != grad_rows:
                    l, logp = loss_fn(p32, _rows(traj, loss_rows))
            return l, logp, g

        def change(p3, p0):
            return sumsq(jax.tree.map(
                lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                p3, to_ref(p0)))

        self._to_ref = jax.jit(to_ref, donate_argnums=0)
        self._step = jax.jit(step)
        self._opt = jax.jit(functools.partial(ref.opt_step, self.spec),
                            donate_argnums=(0, 1, 2))
        self._sumsq = jax.jit(sumsq)
        self._change = jax.jit(change)

    def follow(self, p0, trajs: list[dict]) -> dict:
        """The updates from ``p0`` (donated) -> the losses, the first
        clipped gradient's leaf norms, the first trajectory's log-probs
        under ``p0`` and the parameters after the last update."""
        p = self._to_ref(p0)
        state = ref.opt_init(self.spec, p)
        losses = []
        for k, traj in enumerate(trajs):
            l, logp, g = self._step(p, _to_device(traj))
            p, state, g = self._opt(p, state, g)
            losses.append(float(l))
            common.log(f"reference step {k + 1}: loss {losses[-1]!r}")
            if k == 0:
                logp1 = np.asarray(logp)
                g1 = {kk: math.sqrt(v) for kk, v in _floats(self._sumsq(g)).items()}
            del g
        return {"losses": losses, "g1": g1, "logp1": logp1, "p3": p}

    def change_norms(self, p3, p0) -> dict:
        """Per-leaf norms of p3 (the reference's layout) - p0 (the
        program's), under the program's leaf names."""
        return {k: math.sqrt(v) for k, v in _floats(self._change(p3, p0)).items()}


@jax.jit
def _change_sumsq(p3, p0):
    return _generic_sumsq(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), p3, p0))


def change_norms(p3, p0) -> dict:
    """Per-leaf norms of p3 - p0, both in the program's layout."""
    return {k: math.sqrt(v) for k, v in _floats(_change_sumsq(p3, p0)).items()}


def compare(got: dict, want: dict) -> dict:
    """The readings of one side (``got``) against the reference.  A cell
    compares those its workload file gives a limit."""
    got_logp = np.asarray(got["logp"], np.float64)
    want_logp = np.asarray(want["logp"], np.float64)[: got_logp.shape[0]]
    moved = ref.moved_leaves(want["g1"])
    return {
        "logp_gap": float(np.max(np.abs(got_logp - want_logp))),
        "loss_gap": max(ref.abs_gap(a, b)
                        for a, b in zip(got["losses"], want["losses"])),
        "grad_gap": ref.worst_leaf_gap(got["g1"], want["g1"])[0],
        "change_gap": ref.worst_leaf_gap(got["dp"], want["dp"], moved)[0],
        "grad_gap_median": ref.median_leaf_gap(got["g1"], want["g1"]),
        "change_gap_median": ref.median_leaf_gap(got["dp"], want["dp"], moved),
    }


def _learners(cell) -> int:
    return cell.chips - cell.traffic["num_actor_cores"] if cell.chips > 1 else 1


def _reference_side(cell, seed, trajs, **kw) -> dict:
    key = (cell, "follower", tuple(sorted((k, str(v)) for k, v in kw.items())))
    f = _jit_once(key, lambda: Follower(cell, **kw))
    r = f.follow(make_params(cell, seed), trajs)
    r["dp"] = f.change_norms(r.pop("p3"), make_params(cell, seed))
    r["logp"] = r.pop("logp1")
    return r


def reference_readings(cell, seed: int, probe: Probe,
                       variants=("program",), detail: bool = False) -> dict:
    """Readings of the program, and of any other ``variants`` put in its
    place, against the reference.  Variants: "control" (the reference
    computed at float8), "half_batch" (loss and update over half the
    rows), "no_exchange" (the update from the first learner's shard
    alone), "altered_token" (the first trajectory's actions shifted by one
    after their log-probs were recorded), "unchanged" (the state left as
    it was).  Runs once the program's state is freed.  With ``detail``,
    also the losses and per-leaf norms of every side, under "detail"."""
    B, trajs = cell.traffic["actor_batch_size"], probe.trajs
    want = _reference_side(cell, seed, trajs)
    program = {
        "losses": probe.program_losses(), "g1": probe.g1,
        "dp": change_norms(jax.device_put(probe.p3), make_params(cell, seed)),
        "logp": trajs[0]["behaviour_logp"],
    }
    common.log(f"losses: program {program['losses']!r}, reference "
               f"{want['losses']!r}")
    kw = {
        "control": dict(num=ref.FP8),
        "half_batch": dict(loss_rows=slice(0, B // 2),
                           grad_rows=slice(0, B // 2)),
        "no_exchange": dict(grad_rows=slice(0, B // _learners(cell))),
    }
    out, sides = {}, {"reference": want}
    for v in variants:
        w = want
        if v == "program":
            got = program
        elif v == "unchanged":
            got = dict(program, dp={k: 0.0 for k in want["dp"]})
        elif v == "altered_token":
            A = cell.cfg.get("num_actions") or cell.cfg["vocab_size"]
            alt = dict(trajs[0], actions=(trajs[0]["actions"] + 1) % A)
            got = program
            w = dict(want, logp=_reference_side(cell, seed, [alt])["logp"])
        else:
            got = _reference_side(cell, seed, trajs, **kw[v])
        out[v] = compare(got, w)
        sides[v] = got
    if detail:
        out["detail"] = {
            k: {f: v[f] for f in ("losses", "g1", "dp")}
            for k, v in sides.items()
        }
    return out


# -------------------------------------------------------------------- run


def run(cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, trace_out: str | None = None) -> tuple[dict, dict]:
    counter = common.CompileCounter()
    s = Session(cell, seed, devices)
    s.probe.wait_for(cell.spec["warm_updates"], s.alive)
    common.log(f"update {cell.spec['warm_updates']} dispatched: warm")
    win = s.measure(seconds)
    setup_s = win["t0"] - t_start
    compiles = counter.between(win["t0"], win["t1"])
    common.log(f"window: updates {win['k0']}..{win['k1']} in "
               f"{win['t1'] - win['t0']:.3f} s, backlog "
               f"{win['backlog_frames']} frames, {compiles} compiles inside")
    traced = None
    if trace:
        traced = _traced_window(s, cell.spec["trace_seconds"])
    result = s.stop()
    failed = (result["actor_restarts"] + result["actor_quarantined"]
              + result["watchdog_stalls"])
    device = common.device_record(devices)
    probe = s.probe
    del s, result
    gc.collect()

    t_ref = time.perf_counter()
    readings = reference_readings(cell, seed, probe)["program"]
    common.log(f"reference: {time.perf_counter() - t_ref:.1f} s; persistent "
               f"cache {counter.cache}")
    limits = cell.spec["limits"]
    checks = {
        k: {"value": readings[k], "limit": limits[k],
            "ok": readings[k] <= limits[k]}
        for k in limits
    }
    backlog, cap = max(win["backlog_frames"]), max_backlog(cell.traffic)
    checks["backlog"] = {"value": backlog, "limit": cap, "ok": backlog <= cap}
    checks["actor_failures"] = {"value": failed, "limit": 0,
                                "ok": failed == 0}
    checks["compiles_in_window"] = {"value": compiles, "limit": 0,
                                    "ok": compiles == 0}
    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": win["k1"] - win["k0"],
        "failed": failed,
        "device": device,
        # the raw count and the backlog at both marks, to check the rate by
        "window": {"seconds": win["t1"] - win["t0"],
                   "trained_frames": win["trained_frames"],
                   "backlog_frames": win["backlog_frames"]},
    }
    if trace:
        metrics, extra, breakdown = _per_layer(cell, traced, win, devices,
                                               trace_out)
        result["metrics"] = metrics
        result["device"].update(extra)
        result["breakdown"] = breakdown
    else:
        result["metrics"] = {
            "train_frames_per_s": {"value": win["train_frames_per_s"],
                                   "unit": "frames/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    return result, checks


def _traced_window(s: Session, seconds: float):
    import tempfile

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    devtrace.start(log_dir)
    try:
        s.measure(seconds)
    finally:
        devtrace.stop()
    return log_dir


class Context:
    """What a per-layer metric's reader gets: the cell, the reduced trace,
    the end-to-end rate of this run, and which chips act and learn."""

    def __init__(self, cell, trace, win, devices):
        self.cell, self.cfg, self.traffic = cell, cell.cfg, cell.traffic
        self.cfg_module = cell.cfg_module
        self.trace = trace
        self.train_frames_per_s = win["train_frames_per_s"]
        self.chips = len(devices)
        self.device_ids = [d.id for d in devices]
        a = cell.traffic["num_actor_cores"]
        if len(devices) == 1:
            self.actor_ids = self.learner_ids = self.device_ids
        else:
            self.actor_ids = self.device_ids[:a]
            self.learner_ids = self.device_ids[a:]
        self.peaks = common.load_json(common.BENCH / "peaks.json")[
            devices[0].device_kind]


def _per_layer(cell, traced, win, devices, trace_out=None):
    import shutil

    try:
        trace = devtrace.Trace.from_dir(traced)
    finally:
        shutil.rmtree(traced, ignore_errors=True)
    if trace_out:
        trace.to_json(trace_out)
    ctx = Context(cell, trace, win, devices)
    metrics = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    ids = ctx.device_ids
    busy = [trace.busy_ns(d) / 1e9 for d in ids]
    extra = {"busy_s": sum(busy) / len(busy),
             "window_s": trace.window_ns / 1e9}
    for d, b in zip(ids, busy):
        common.log(f"chip {d}: busy {b!r} s of {extra['window_s']!r} s")
    return metrics, extra, trace.breakdown(ids)
