"""Drives a whole run at tiny widths with the look for a chip skipped
and the timed path broken underneath: `correct` has to come out false,
once for each fault the cells can have; and true where nothing is broken.
The exchange between chips has no cell yet (every cell is on one chip),
and no cell produces tokens or answers one by one.
"""

import time

import pytest

from benchmark import run
from benchmark.rehearse.tiny import shrink

TRAIN = "mistral7b.qlora_sft_1k"


def drive(workload, seconds=1.0):
    out = run.run_cell(workload, seed=2 ** 31 + 7, seconds=seconds,
                       trace=False, require_chip=False,
                       t_start=time.perf_counter(), override=shrink)
    return out["result"]["correct"], out["checks"]


def break_train_step(monkeypatch, wrap):
    import gke_ray_train_tpu.train as train_pkg
    real_factory = train_pkg.make_train_step

    def factory(*args, **kwargs):
        return wrap(real_factory(*args, **kwargs))
    monkeypatch.setattr(train_pkg, "make_train_step", factory)


def test_a_sound_run_is_correct():
    ok, checks = drive(TRAIN)
    assert ok, checks


def test_state_returned_unchanged(monkeypatch):
    def wrap(real):
        def step(state, batch):
            _, metrics = real(state, batch)
            return state, metrics
        return step
    break_train_step(monkeypatch, wrap)
    ok, checks = drive(TRAIN)
    assert not ok
    # nothing reached the optimizer: the gap of norms reads 1
    assert checks["grad_gap"][0] == pytest.approx(1.0, abs=1e-3)
    assert checks["change_gap"][0] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out(monkeypatch):
    import jax.numpy as jnp

    def wrap(real):
        def step(state, batch):
            rows = batch["weights"].shape[0]
            keep = (jnp.arange(rows) < rows // 2)[:, None]
            return real(state, dict(batch, weights=jnp.where(
                keep, batch["weights"], 0.0)))
        return step
    break_train_step(monkeypatch, wrap)
    ok, checks = drive(TRAIN)
    assert not ok, checks
