"""The training check, driven end to end at a tiny size on the CPU: a
sound run is correct and the fp8 control is not; a step that
returns its state unchanged, and a step that leaves out half the batch
(the mean taken over the rest), make ``correct`` false."""
from __future__ import annotations

import pytest

import tiny
from bench import spec
from bench.drivers import train


def _ctx(monkeypatch, seed=3):
    monkeypatch.setattr(spec, "program_config", tiny.program_config)
    return tiny.context("train.bertl.1chip", seed=seed, seconds=0.5,
                        batch=4, seq=32)


def test_sound_run_is_correct_and_the_control_is_not(monkeypatch):
    ctx = _ctx(monkeypatch, seed=2 ** 31 + 77)
    assert train.run(ctx, control=True)
    assert ctx.attempted >= 1 and ctx.failed == 0
    ctrl = ctx.readings["control"]
    lim = ctx.cell["limits"]
    # the control has to fail one of the numbers, not each
    assert (ctrl["loss_gap"] > lim["loss_gap"]
            or ctrl["grad_gap"][0] > lim["grad_gap"]
            or ctrl["delta_gap"][0] > lim["delta_gap"])


def _broken(monkeypatch, wrap):
    from repro.runtime import train_loop

    make = train_loop.make_train_step
    monkeypatch.setattr(train_loop, "make_train_step",
                        lambda *a, **k: wrap(make(*a, **k)))


def test_unchanged_state_is_caught(monkeypatch):
    def wrap(step):
        def frozen(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return frozen
    _broken(monkeypatch, wrap)
    ctx = _ctx(monkeypatch)
    assert not train.run(ctx)
    assert ctx.checks["delta_gap"][0] == pytest.approx(1.0)
    assert ctx.checks["grad_gap"][0] == pytest.approx(1.0)


def test_half_batch_is_caught(monkeypatch):
    def wrap(step):
        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    _broken(monkeypatch, wrap)
    ctx = _ctx(monkeypatch)
    assert not train.run(ctx)
    assert any(v > lim for v, lim in ctx.checks.values())
