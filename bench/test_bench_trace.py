"""The trace reduction on synthetic event lists."""
from __future__ import annotations

import pytest

import trace_reduce as tr


def test_union_merges_overlaps_and_drops_empty():
    got = tr.union([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12), (11, 13)])
    assert got == [(0, 4), (5, 10), (11, 13)]


def test_gaps_cover_what_busy_does_not():
    busy = tr.union([(2, 4), (6, 7)])
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps([(0, 10)], 0, 10) == []
    assert tr.gaps([], 3, 5) == [(3, 5)]


def test_gap_goes_to_innermost_open_span():
    spans = [("bench.engine_step", 0, 100), ("bench.plan_build", 10, 40),
             ("bench.client", 100, 120)]
    idle = [(12, 20), (50, 60), (105, 115), (130, 140)]
    assert tr.attribute(idle, spans) == {"bench.plan_build": 8,
                                         "bench.engine_step": 10,
                                         "bench.client": 10, "none": 10}


def test_reduce_clips_to_window_and_averages_devices():
    spans = [("bench.window", 100, 200), ("bench.plan_build", 100, 150),
             ("bench.dispatch", 150, 200)]
    ops = {
        "/device:TPU:0": [("spconv_gemm_fused.3", 90, 120),
                          ("fusion.12", 160, 170),
                          ("octent_query", 170, 180),
                          ("fusion.7", 175, 190), ("late", 250, 260)],
        "/device:TPU:1": [("spconv_gemm_fused.1", 100, 200)],
    }
    s = tr.reduce(ops, spans, kernels=("spconv_gemm_fused", "octent_query"))
    assert s["window_s"] == pytest.approx(100e-9)
    # device 0 busy [100,120) + [160,190) = 50 ns, device 1 100 ns
    assert s["busy_s"] == pytest.approx(75e-9)
    assert s["kernel_s"]["spconv_gemm_fused"] == pytest.approx(60e-9)
    assert s["kernel_s"]["octent_query"] == pytest.approx(5e-9)
    assert s["kernel_calls"] == {"spconv_gemm_fused": 2, "octent_query": 1}
    fams = dict(s["device_ops"])
    assert fams["fusion"] == pytest.approx(25e-9 / 2)
    assert "late" not in fams
    # device 0 idle: [120,160), whose middle lies in the plan build, and
    # [190,200) in dispatch; device 1 never idle
    idle = dict(s["idle_gaps"])
    assert idle["plan_build"] == pytest.approx(40e-9 / 2)
    assert idle["dispatch"] == pytest.approx(10e-9 / 2)


def test_reduce_without_window_or_devices_reads_nothing():
    assert tr.reduce({}, [("bench.window", 0, 1)]) == {}
    assert tr.reduce({"/device:TPU:0": [("x", 0, 1)]}, []) == {}


def test_op_family_strips_numeric_suffixes():
    assert tr.op_family("fusion.12") == "fusion"
    assert tr.op_family("copy.1.2") == "copy"
    assert tr.op_family("spconv_gemm_fused") == "spconv_gemm_fused"
