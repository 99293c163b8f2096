"""A run whose timed path is broken underneath comes out not correct.

Each test drives the harness's own run at the smoke widths on the CPU
(everything but the look for a chip) with one fault planted in the served
program, and expects ``correct`` false: a denoising step that returns its
state unchanged; half of the batch left out, its slots filled from the rest;
an answer altered where it is produced; rollback that corrects nothing.
The exchange between chips has no fault to plant: every cell runs on one
chip.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from test_chipbench_correct import smoke_run  # noqa: E402

DRIFT = "dit-xl-512.drift-mild-closed8"


def _program():
    from chipbench import runner
    runner.import_program()
    from repro.core import rollback
    from repro.diffusion import schedule
    from repro.serving import servable
    return rollback, schedule, servable


def _wrap_execute(monkeypatch, servable, edit):
    execute = servable.DiffusionServable.execute

    def broken(self, mb, ctx):
        out = execute(self, mb, ctx)
        return out._replace(latents=edit(out.latents))
    monkeypatch.setattr(servable.DiffusionServable, "execute", broken)


def test_a_step_that_returns_its_state_unchanged(monkeypatch):
    _, schedule, _ = _program()
    monkeypatch.setattr(schedule.DdpmSchedule, "ddim_step",
                        lambda self, x_t, eps, t, t_prev: x_t)
    out = smoke_run(DRIFT, 11)
    assert not out["correct"], out["checks"]


def test_half_of_the_batch_left_out(monkeypatch):
    _, _, servable = _program()

    def first_half(lat):
        half = lat.shape[0] // 2
        return jnp.concatenate([lat[:half], lat[:lat.shape[0] - half]])
    _wrap_execute(monkeypatch, servable, first_half)
    out = smoke_run(DRIFT, 12)
    assert not out["correct"], out["checks"]


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    _, _, servable = _program()
    # served finals are clipped to [-1, 1], so scaling a saturated latent
    # would not show; a quarter of one request's latent changes sign
    _wrap_execute(monkeypatch, servable,
                  lambda lat: lat.at[0, :lat.shape[1] // 2,
                                     :lat.shape[2] // 2].multiply(-1.0))
    out = smoke_run("dit-xl-512.clean-closed8", 13)
    assert not out["correct"], out["checks"]


def test_rollback_that_corrects_nothing(monkeypatch):
    rollback, _, _ = _program()
    monkeypatch.setattr(rollback, "correct",
                        lambda current, checkpoint, mask, have: current)
    out = smoke_run(DRIFT, 14)
    assert not out["correct"], out["checks"]
