"""A run whose timed path is broken comes out not correct.

Each test drives the whole harness at ``cfg.smoke()`` size on the CPU
(its look for a chip aside) with one fault planted in the program:

* a step that returns its state unchanged;
* half of each batch left out, the mean taken over the rest;
* the exchange between agents (the sync) left out.

A training cell produces no tokens or answers, so there is no altered
token or answer to plant.
"""
import time

import jax
import pytest

from harness.runner import run_cell
from harness.spec import cell_names, load_cell

CELLS = cell_names()


def _run(name):
    return run_cell(load_cell(name), 2 ** 31 + 777, 0.3, False,
                    devices=jax.devices()[:1], t_start=time.perf_counter(),
                    smoke=True, log=lambda *a: None)


def unchanged_state(monkeypatch):
    from repro.core.fedgan import FedGAN
    step = FedGAN._step

    def frozen(self, state, step_input):
        _, metrics = step(self, state, step_input)
        return state, metrics

    monkeypatch.setattr(FedGAN, "_step", frozen)


def half_batch(monkeypatch):
    from repro.data.federated import DeviceFederatedData
    sample = DeviceFederatedData.sample_step

    def half(self, key):
        batch = sample(self, key)
        return jax.tree.map(lambda x: x[:, :, :x.shape[2] // 2], batch)

    monkeypatch.setattr(DeviceFederatedData, "sample_step", half)


def no_sync(monkeypatch):
    from repro.core.strategies import FedAvgSync
    monkeypatch.setattr(FedAvgSync, "round_sync", lambda self, fed, st: st)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, no_sync],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(name)
    assert not out["correct"], out["checks"]
