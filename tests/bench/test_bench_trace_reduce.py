"""``bench/trace_reduce.py``: interval arithmetic on hand-made events,
and the reduction of a small trace recorded on a TPU v5e chip."""
from __future__ import annotations

import os

import pytest

import tiny
from bench import trace_reduce as tr

MS = 1_000_000  # ns


def test_union_subtract_and_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.gaps([(1, 2), (4, 6)], 0, 8) == [(0, 1), (2, 4), (6, 8)]
    assert tr.length(tr.clip([(0, 4), (6, 9)], 1, 7)) == 4


def test_busy_exposed_collectives_and_gap_attribution():
    a2a = "%all-to-all.2 = bf16[4,8]{1,0} all-to-all(bf16[4,8]{1,0} %x)"
    kern = ('%closed_call.3 = bf16[32,8]{1,0:T(8,128)} custom-call(s32[32] '
            '%p), custom_call_target="tpu_custom_call"')
    dev = {
        "/device:TPU:0": [
            (0 * MS, 4 * MS, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)"),
            (0 * MS, 9 * MS, "%while.4 = (s32[], f32[8]) while((s32[], "
                             "f32[8]) %t), body=%b"),  # spans the others
            (3 * MS, 6 * MS, a2a),                    # 2 ms exposed
            (8 * MS, 9 * MS, kern),
        ],
        "/device:TPU:1": [
            (0 * MS, 10 * MS, a2a),                   # all exposed
        ],
    }
    modules = {"/device:TPU:0": [(0, 7 * MS, "jit_a"),
                                 (7 * MS, 10 * MS, "jit__decode_step")]}
    host = [(0, 10 * MS, "bench.window"),
            (6 * MS, 8 * MS, "bench.metrics_sync"),
            (5 * MS, 9 * MS, "bench.train_step")]
    red = tr.reduce_events(dev, host, 0, 10 * MS, modules)
    d0, d1 = red["devices"]["/device:TPU:0"], red["devices"]["/device:TPU:1"]
    assert d0["busy_s"] == pytest.approx(9e-3)
    assert d0["exposed_collective_s"] == pytest.approx(2e-3)
    assert d1["exposed_collective_s"] == pytest.approx(10e-3)
    assert red["busy_s"] == pytest.approx(9.5e-3)
    assert red["window_s"] == pytest.approx(10e-3)
    gaps = dict(red["idle_gaps"])
    # device 0 idles 9-10 ms only (the while loop spans 0-9 ms), outside
    # any inner span; averaged over two devices
    assert gaps == {"no bench span": pytest.approx(0.5e-3)}
    ops = dict(red["top_ops"])
    assert ops["all-to-all.2 bf16[4,8] all-to-all"] == pytest.approx(6.5e-3)
    assert not any("while" in k for k in ops)
    assert red["by_kind_s"]["custom_call"] == pytest.approx(0.5e-3)
    assert red["custom_call_s_by_program"] == {
        "jit__decode_step": pytest.approx(0.5e-3)}


def test_gap_goes_to_the_innermost_span():
    dev = {"/device:TPU:0": [(0, 2 * MS, "%f.1 = f32[1]{0} fusion()"),
                             (8 * MS, 10 * MS, "%f.2 = f32[1]{0} fusion()")]}
    host = [(0, 10 * MS, "bench.window"),
            (1 * MS, 9 * MS, "bench.train_step"),
            (3 * MS, 7 * MS, "bench.metrics_sync")]
    red = tr.reduce_events(dev, host, 0, 10 * MS)
    assert dict(red["idle_gaps"]) == {
        "bench.metrics_sync": pytest.approx(6e-3)}
    assert red["busy_s"] == pytest.approx(4e-3)


def test_parse_op():
    assert tr.parse_op("%convert.35 = bf16[6,64]{1,0:T(8,128)(2,1)} "
                       "convert(f32[6,64]{1,0} %p)") == \
        ("convert.35", "bf16[6,64]", "convert")
    assert tr.parse_op("%fusion.2 = (f32[2]{0:T(128)}, bf16[2]{0}) "
                       "fusion(f32[2]{0} %a), kind=kOutput")[2] == "fusion"


TINY = os.path.join(tiny.ROOT, "bench", "testdata", "tiny_v5e.xplane.pb")


def test_recorded_v5e_trace():
    """Four steps of two bf16 1024x1024 matmuls recorded on one v5e
    chip, each step inside ``bench.batch_upload`` (2 ms sleep),
    ``bench.train_step`` and ``bench.metrics_sync`` (3 ms sleep)."""
    pd = tr.load(TINY)
    ops = []
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops = [(e.start_ns, e.duration_ns) for e in line.events]
    red = tr.reduce(pd)
    # the ops never overlap, so busy is their summed time
    assert len(ops) == 16
    assert red["busy_s"] == pytest.approx(sum(d for _, d in ops) * 1e-9)
    assert red["busy_s"] == pytest.approx(9.6847e-05)
    assert red["window_s"] == pytest.approx(0.03277483)
    gaps = dict(red["idle_gaps"])
    assert set(gaps) == {"bench.metrics_sync", "bench.batch_upload"}
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert gaps["bench.metrics_sync"] > gaps["bench.batch_upload"] > 0.004
    assert red["devices"]["/device:TPU:0"]["collective_s"] == 0.0
    top = red["top_ops"]
    assert top[0][0] == "fusion bf16[1024,1024] fusion"
    assert top[1][0] == "convolution_tanh_fusion bf16[1024,1024] fusion"
