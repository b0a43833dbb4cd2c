"""``correct`` is shown to fail: the control (the reference computed at
float8 in the program's place) and each fault a cell can have, planted in
the program's timed path, come out not correct.

The runs skip the look for a chip and drive the rest of a run on the CPU
at a small size.  At that size the program's sound readings are not those
of the chip (a few thousand bfloat16 weights round differently from
1.5B), so each check's limit here is the cell's limit or three times the
sound run's reading, whichever is larger; a fault has to read above both.
The four-chip cell, which the tests describe (conftest), borrows the
one-chip cell's limits.
"""

import time

import jax
import numpy as np
import pytest

from bench.drivers import sebulba as drv

CELLS = ["lmrl-qwen2-copy64", "impala-deep-pong84-x4"]


def sound_readings(cell, devices, seed):
    s = drv.Session(cell, seed, devices)
    s.probe.wait_for(drv.CAPTURED + 1, s.alive)
    s.stop()
    probe = s.probe
    del s
    return drv.reference_readings(cell, seed, probe, ["program", "control"])


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


FAULTS = {
    "unchanged": fault_unchanged,
    "half_batch": fault_half_batch,
    "no_exchange": fault_no_exchange,
    "altered_token": fault_altered_token,
}


# the exchange between learner chips exists only in the four-chip cell
CASES = [(name, fault) for name in CELLS for fault in sorted(FAULTS)
         if fault != "no_exchange" or name.endswith("-x4")]


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
    assert np.isfinite(result["metrics"]["train_frames_per_s"]["value"])
