"""``bench/program_trace.py``: device time by program scope, exposed
All-to-All and idle gaps by program span, on hand-made events; and the
small v5e trace, whose program the scope table does not know."""
from __future__ import annotations

import os
import shutil
import types

import pytest

import tiny
from bench import program_trace as pt

MS = 1_000_000  # ns


def fusion(name):
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %a), kind=kLoop"


def a2a(name):
    return f"%{name} = bf16[4,8]{{1,0}} all-to-all(bf16[4,8]{{1,0}} %x)"


TABLE = {"jit_train_step": {"fusion.1": "moe.expert_ffn",
                            "fusion.2": "optimizer",
                            "all-to-all.3": "moe.a2a_dispatch",
                            "all-to-all.4": "moe.a2a_combine",
                            "all-reduce.5": "optimizer"}}


def test_scope_time_over_busy_time():
    dev = {"/device:TPU:0": [
        (0 * MS, 4 * MS, fusion("fusion.1")),
        (0 * MS, 9 * MS, "%while.7 = (s32[]) while((s32[]) %t), body=%b"),
        (4 * MS, 6 * MS, fusion("fusion.2")),
        (6 * MS, 8 * MS, fusion("fusion.9")),          # no scope
    ]}
    mods = {"/device:TPU:0": [(0, 10 * MS, "jit_train_step")]}
    s = pt.summarize(dev, mods, [], 0, 10 * MS, TABLE)
    assert s["busy_s"] == pytest.approx(8e-3)
    assert s["unscoped_s"] == pytest.approx(2e-3)
    assert pt.scope_share(s, "moe.expert_ffn") == pytest.approx(50.0)
    assert pt.scope_share(s, "optimizer") == pytest.approx(25.0)
    assert pt.scope_share(s, "sample") == 0.0


def test_exposed_all_to_all_is_the_worst_chips():
    dev = {
        # dispatch 2-5 ms, 1 ms under the expert GEMM: 2 ms exposed; the
        # combine 7-8 ms alone: 1 ms; an all-reduce outside the a2a
        # scopes is not counted
        "/device:TPU:0": [(0, 3 * MS, fusion("fusion.1")),
                          (2 * MS, 5 * MS, a2a("all-to-all.3")),
                          (7 * MS, 8 * MS, a2a("all-to-all.4")),
                          (8 * MS, 9 * MS,
                           "%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0}"
                           " %g), to_apply=%add")],
        "/device:TPU:1": [(0, 1 * MS, a2a("all-to-all.3")),
                          (0, 1 * MS, fusion("fusion.1"))],
    }
    mods = {d: [(0, 10 * MS, "jit_train_step")] for d in dev}
    s = pt.summarize(dev, mods, [], 0, 10 * MS, TABLE)
    assert s["a2a_exposed_s"]["/device:TPU:0"] == pytest.approx(3e-3)
    assert s["a2a_exposed_s"]["/device:TPU:1"] == pytest.approx(0.0)
    assert pt.a2a_exposed_share(s) == pytest.approx(30.0)


def test_idle_gaps_go_to_the_innermost_program_span():
    dev = {"/device:TPU:0": [(0, 1 * MS, fusion("fusion.1")),
                             (4 * MS, 5 * MS, fusion("fusion.2"))]}
    mods = {"/device:TPU:0": [(0, 10 * MS, "jit_train_step")]}
    host = [(0, 8 * MS, "engine.step"),
            (0, 1 * MS, "engine.dispatch"),
            (1 * MS, 3 * MS, "engine.sync"),
            (3 * MS, 6 * MS, "engine.emit"),
            (3 * MS, 4 * MS, "host.gc")]
    s = pt.summarize(dev, mods, host, 0, 10 * MS, TABLE)
    # gaps: 1-4 ms (middle 2.5, in engine.sync), 5-10 ms (middle 7.5, in
    # engine.step only)
    assert s["gap_s"] == {"engine.sync": pytest.approx(3e-3),
                          "engine.step": pytest.approx(5e-3)}
    assert pt.host_gap_share(s) == pytest.approx(80.0)
    # a gap outside every program span counts nowhere
    s = pt.summarize(dev, mods, [(0, 3 * MS, "engine.step")], 0, 10 * MS,
                     TABLE)
    assert s["gap_s"] == {"engine.step": pytest.approx(3e-3)}
    assert pt.host_gap_share(s) == pytest.approx(30.0)


def test_gc_pause_holds_its_gap():
    dev = {"/device:TPU:0": [(0, 1 * MS, fusion("fusion.1")),
                             (3 * MS, 4 * MS, fusion("fusion.2"))]}
    host = [(0, 4 * MS, "engine.step"), (1 * MS, 3 * MS, "engine.inputs"),
            (1.5 * MS, 2.5 * MS, "host.gc")]
    s = pt.summarize(dev, {}, host, 0, 4 * MS, TABLE)
    assert s["gap_s"] == {"host.gc": pytest.approx(2e-3)}


def test_a_lost_table_reads_none_not_zero():
    dev = {"/device:TPU:0": [(0, 4 * MS, fusion("fusion.1")),
                             (4 * MS, 8 * MS, fusion("fusion.2"))]}
    mods = {"/device:TPU:0": [(0, 10 * MS, "jit_train_step")]}
    for table in (None, {}, {"jit_other": {}}):
        s = pt.summarize(dev, mods, [], 0, 10 * MS, table)
        assert pt.scope_share(s, "optimizer") is None
        assert pt.a2a_exposed_share(s) is None
        assert pt.host_gap_share(s) is None       # no program span
    # a program the table lacks, under the limit, leaves the share
    mods = {"/device:TPU:0": [(0, 7.99 * MS, "jit_train_step"),
                              (7.99 * MS, 10 * MS, "jit_helper")]}
    dev["/device:TPU:0"].append((7.995 * MS, 8.0 * MS, fusion("f.3")))
    s = pt.summarize(dev, mods, [], 0, 10 * MS, TABLE)
    assert s["unknown_s"] == {"jit_helper": pytest.approx(0.005e-3)}
    assert pt.scope_share(s, "optimizer") == pytest.approx(50.0)


def test_the_v5e_trace_of_an_unknown_program(tmp_path, monkeypatch):
    """The recorded trace ran ``jit__lambda``, which no table holds:
    every reader returns None, and the summary is read once."""
    src = os.path.join(tiny.ROOT, "bench", "testdata", "tiny_v5e.xplane.pb")
    shutil.copy(src, tmp_path / "run.xplane.pb")
    ctx = types.SimpleNamespace(trace_dir=str(tmp_path))
    for table in ({}, None):
        monkeypatch.setattr(pt, "_table", lambda t=table: t)
        pt._CACHE.clear()
        s = pt.summary(ctx)
        assert s["busy_s"] > 0 and s["devices"] == 1
        assert pt.scope_share(s, "sample") is None
        assert pt.a2a_exposed_share(s) is None
        assert pt.host_gap_share(s) is None
        assert pt.summary(ctx) is s
