"""``correct`` is shown to fail: the control (the reference computed at
float8 in the program's place) and each fault a cell can have, planted in
the program's timed path, come out not correct.

The runs skip the look for a chip and drive the rest of a run on the CPU
at a small size.  At that size the program's sound readings are not those
of the chip (a few thousand bfloat16 weights round differently from
1.5B), so each check's limit here is the cell's limit or three times the
sound run's reading, whichever is larger; a fault has to read above both.
"""

import time

import jax
import numpy as np
import pytest

from bench import common
from bench.drivers import sebulba as drv

CELLS = ["lmrl-qwen2-copy64", "lmrl-qwen2-learn", "impala-deep-pong84-x4"]
SIGNED = "signed_change_gap_median"


def sound_readings(cell, devices, seed, variants=("control",)):
    s = drv.Session(cell, seed, devices)
    s.probe.wait_for(drv.CAPTURED + 1, s.alive)
    s.stop()
    probe = s.probe
    del s
    return drv.reference_readings(cell, seed, probe, ["program", *variants])


@pytest.fixture(scope="module")
def calibrated():
    """Each cell's sound and control readings at the test size."""
    return {}


def limits_for(cell, sound):
    return {k: max(v, 3 * sound[k]) for k, v in cell.spec["limits"].items()}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, tiny_cell, cpu_devices, calibrated):
    cell = tiny_cell(name)
    r = sound_readings(cell, cpu_devices(cell), seed=11)
    calibrated[name] = r["program"]
    limits = limits_for(cell, r["program"])
    assert all(r["program"][k] <= limits[k] for k in limits)
    failed = [k for k in limits if not r["control"][k] <= limits[k]]
    assert failed, f"the control passed every check: {r['control']}"


def fault_unchanged(monkeypatch):
    from repro import optim

    monkeypatch.setattr(optim, "apply_updates", lambda params, updates: params)


def fault_sign_flip(monkeypatch):
    from repro import optim

    def apply_updates(params, updates):
        return jax.tree.map(
            lambda p, u: (p.astype(np.float32) - u.astype(np.float32))
            .astype(p.dtype), params, updates)

    monkeypatch.setattr(optim, "apply_updates", apply_updates)


def fault_half_batch(monkeypatch):
    from repro.agents.impala import ImpalaAgent
    from repro.agents.lm_policy import LMPolicyAgent

    for cls in (ImpalaAgent, LMPolicyAgent):
        orig = cls.loss

        def loss(self, params, traj, weights=None, orig=orig):
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], traj)
            return orig(self, params, half, weights)

        monkeypatch.setattr(cls, "loss", loss)


def fault_no_exchange(monkeypatch):
    from repro import optim
    from repro.core.sebulba import Sebulba

    def sgd_step(self, params, opt_state, loss_fn):
        grads, aux = jax.grad(loss_fn, has_aux=True)(params)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        return optim.apply_updates(params, updates), opt_state, aux

    monkeypatch.setattr(Sebulba, "_sgd_step", sgd_step)


def fault_altered_token(monkeypatch):
    from repro.agents.impala import ImpalaAgent
    from repro.agents.lm_policy import LMPolicyAgent

    for cls in (ImpalaAgent, LMPolicyAgent):
        orig = cls.act

        def act(self, params, obs, rng, carry=(), orig=orig):
            actions, aux, carry = orig(self, params, obs, rng, carry)
            n = getattr(getattr(self, "net", None), "num_actions", None)
            n = n or self.cfg.vocab_size
            return (actions + 1) % n, aux, carry

        monkeypatch.setattr(cls, "act", act)


def fault_dropped(monkeypatch):
    """The learner takes every trajectory from the queue and trains on
    every other one: the actors' count runs on, the backlog grows."""
    import itertools

    from repro.core.sebulba import Sebulba

    orig = Sebulba.__init__

    def init(self, *args, **kwargs):
        orig(self, *args, **kwargs)
        get, n = self._queue.get, itertools.count()

        def get_every_other(*a, **kw):
            while next(n) % 2:
                get(*a, **kw)
            return get(*a, **kw)

        self._queue.get = get_every_other

    monkeypatch.setattr(Sebulba, "__init__", init)


FAULTS = {
    "unchanged": fault_unchanged,
    "sign_flip": fault_sign_flip,
    "dropped": fault_dropped,
    "half_batch": fault_half_batch,
    "no_exchange": fault_no_exchange,
    "altered_token": fault_altered_token,
}


def compares(name: str, number: str) -> bool:
    spec = common.load_json(common.BENCH / "workloads" / f"{name}.json")
    return number in spec["limits"]


# the exchange between learner chips exists only in the four-chip cell; a
# flipped sign changes no norm, so only a cell that compares the signed
# change can see it
CASES = [(name, fault) for name in CELLS for fault in sorted(FAULTS)
         if (fault != "no_exchange" or name.endswith("-x4"))
         and (fault != "sign_flip" or compares(name, SIGNED))]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(name, fault, tiny_cell, cpu_devices,
                              calibrated, monkeypatch):
    cell = tiny_cell(name)
    sound = calibrated.get(name)
    if sound is None:
        sound = sound_readings(cell, cpu_devices(cell), seed=11)["program"]
        calibrated[name] = sound
    cell.spec["limits"] = limits_for(cell, sound)
    FAULTS[fault](monkeypatch)
    result, checks = cell.driver.run(cell, 13, 1.0, False, cpu_devices(cell),
                                     time.perf_counter())
    assert result["correct"] is False, checks
    broken = [k for k, c in checks.items() if not c["ok"]]
    assert broken
    if fault == "sign_flip":
        assert SIGNED in broken, checks
    assert np.isfinite(result["metrics"]["train_frames_per_s"]["value"])


def test_signed_change_sees_a_flipped_update(tiny_cell, cpu_devices):
    """A sound run reads the signed change far under the cell's limit; the
    same run with every update's sign flipped, in the reference put in the
    program's place, reads about 2 (the change less its own negative),
    above it, while the norms of its change match the reference's."""
    cell = tiny_cell("impala-deep-pong84-x4")
    r = sound_readings(cell, cpu_devices(cell), seed=17, variants=["sign_flip"])
    limit = cell.spec["limits"][SIGNED]
    assert r["program"][SIGNED] < limit / 10
    assert r["sign_flip"][SIGNED] > 1.5 > limit
    assert r["sign_flip"]["grad_gap"] < 1e-3
