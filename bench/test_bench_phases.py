"""The phase reduction and its per-layer readers on synthetic events."""
from __future__ import annotations

import os
import shutil

import pytest

import phases
import run
from repro.runtime import guard

KERNELS = ("octent_query", "spconv_gemm_fused")
READERS = ("tile_build_ms", "search_device_ms", "forward_xla_ms",
           "plan_idle_ms")


def test_ops_take_the_span_that_launched_their_run():
    spans = [("serve.build", 0, 100), ("plan.tiles", 10, 20),
             ("plan.search", 30, 40), ("serve.dispatch", 60, 70)]
    launches = [65, 12, 35]                 # host order: tiles, search, fwd
    runs = [(50, 60), (20, 30), (80, 120)]  # device order = launch order
    ops = [(22, 25, None), (52, 55, "octent_query"), (81, 90, None),
           (90, 110, "spconv_gemm_fused"), (70, 75, None)]
    assert phases.label_ops(runs, ops, launches, spans) == [
        (22, 25, "plan.tiles", None), (52, 55, "plan.search", "octent_query"),
        (81, 90, "serve.dispatch", None),
        (90, 110, "serve.dispatch", "spconv_gemm_fused"),
        (70, 75, "none", None)]             # between runs
    assert phases.label_ops(runs[:2], ops, launches, spans) is None
    assert phases.label_ops([(0, 5)], [(1, 2, None)], [200],
                            spans)[0][2] == "none"


def test_kernel_found_in_the_op_name_or_its_hlo_text():
    class Ev:
        def __init__(self, name, stats=()):
            self.name, self.stats = name, list(stats)

    gemm = Ev("%spconv_gemm_fused.3 = f32[8,128] custom-call(f32[8] %p)")
    call = Ev("%fusion.2 = s32[27,8] fusion(s32[8] %q)",
              [("long_name", "custom_call_target=octent_query")])
    assert phases.kernel_of(gemm, KERNELS) == "spconv_gemm_fused"
    assert phases.kernel_of(call, KERNELS) == "octent_query"
    assert phases.kernel_of(Ev("%fusion.7 = s32[8] add()"), KERNELS) is None


def _synthetic():
    """Two devices over a window [100, 300); spans of one cloud."""
    spans = [("serve.build", 100, 190), ("plan.build", 105, 185),
             ("plan.subm3", 110, 180), ("plan.fingerprint", 112, 118),
             ("plan.search", 120, 125), ("plan.tiles", 126, 130),
             ("serve.dispatch", 192, 194), ("serve.fetch", 265, 290)]
    launches = [113, 121, 127, 193]
    dev0 = ([(140, 170), (90, 105), (200, 265), (105, 135)],
            [(90, 105, None),                 # fingerprint, clipped
             (105, 115, None),                # search
             (115, 135, "octent_query"),      # search kernel
             (140, 160, None), (160, 170, None),   # tiles
             (200, 240, "spconv_gemm_fused"),  # forward kernel
             (240, 260, None), (262, 265, None),   # forward XLA
             (310, 320, None)])               # after the window
    dev1 = ([(0, 1), (1, 2), (2, 3), (100, 300)],
            [(100, 300, None)])               # forward, the whole window
    return ({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, launches,
            spans, (100, 300))


def test_reduce_attributes_device_time_and_idle_to_program_phases():
    s = phases.reduce(*_synthetic())
    ns = pytest.approx
    assert s["window_s"] == ns(200e-9) and s["devices"] == 2
    assert s["paired"] and s["spanned"]
    # device 0 busy: 35 + 30 + 60 + 3 = 128; device 1: 200
    assert s["busy_s"] == ns(164e-9)
    assert s["tiles_s"] == ns(15e-9)
    assert s["search_s"] == ns(15e-9)                # op + OCTENT kernel
    assert s["forward_xla_s"] == ns(111.5e-9)        # 20 + 3, then 200
    assert s["attributed_s"] == ns(164e-9)
    assert s["label_s"]["serve.dispatch"] == ns(131.5e-9)
    assert s["label_s"]["plan.fingerprint"] == ns(2.5e-9)
    # device 0 idle: [135,140) mid 137.5 in plan.subm3, [170,200) mid 185
    # in serve.build, [260,262) in none, [265,300) in serve.fetch
    assert s["idle_s"]["plan.subm3"] == ns(2.5e-9)
    assert s["idle_s"]["serve.build"] == ns(15e-9)
    assert s["idle_s"]["none"] == ns(1e-9)
    assert s["idle_s"]["serve.fetch"] == ns(17.5e-9)
    assert s["plan_idle_s"] == ns(2.5e-9)


def test_reduce_without_spans_or_pairing_attributes_nothing():
    devices, launches, spans, window = _synthetic()
    bare = phases.reduce(devices, launches, [], window)
    assert not bare["spanned"] and bare["paired"]
    assert bare["tiles_s"] == bare["plan_idle_s"] == 0
    lost = phases.reduce(devices, launches[:-1], spans, window)
    assert not lost["paired"]
    assert lost["attributed_s"] == pytest.approx(30e-9)   # kernels alone
    assert lost["busy_s"] == pytest.approx(164e-9)
    assert phases.reduce({}, launches, spans, window) == {}
    assert phases.reduce(devices, launches, spans, None) == {}


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """The readers in a checkout whose harness left a trace file behind;
    the trace's contents come from ``phases.load``, patched per test."""
    shutil.copytree(os.path.join(run.HERE, "metrics"),
                    tmp_path / "bench" / "metrics")
    trace = tmp_path / ".bench_run" / "trace" / "plugins"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(phases, "_memo", {})
    return tmp_path


def _read_all(root, ctx):
    return {name: run.metric_reader(str(root), name)(ctx)
            for name in READERS}


CTX = {"trace": {"kernel_s": dict.fromkeys(KERNELS, 1.0)}, "clouds": 5}


def test_readers_divide_by_the_windows_clouds(checkout, monkeypatch):
    monkeypatch.setattr(phases, "load", lambda d, k=(): _synthetic())
    assert _read_all(checkout, CTX) == pytest.approx({
        "tile_build_ms": 15e-6 / 5, "search_device_ms": 15e-6 / 5,
        "forward_xla_ms": 111.5e-6 / 5, "plan_idle_ms": 2.5e-6 / 5})


def test_readers_read_nothing_from_an_unmarked_program(checkout,
                                                       monkeypatch):
    devices, launches, _, window = _synthetic()
    monkeypatch.setattr(phases, "load",
                        lambda d, k=(): (devices, launches, [], window))
    assert _read_all(checkout, CTX) == dict.fromkeys(READERS)
    assert _read_all(checkout, dict(CTX, trace={})) == dict.fromkeys(READERS)


def test_host_syncs_per_cloud_from_the_health_counters():
    read = run.metric_reader(run.ROOT, "plan_host_syncs")
    with guard.scoped_health() as h:
        assert read({}) is None
        h.note("serve.completed", 5)
        assert read({}) is None
        h.note("plan.host_sync", 300)
        assert read({}) == 60.0
