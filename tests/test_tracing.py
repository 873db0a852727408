"""The program's own trace instrumentation: host spans of the serve engine
and the plan build, device scopes on the plan-build programs and the
forward, and the ``plan.host_sync`` counter."""
from __future__ import annotations

import functools
import glob
import os
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import mapsearch, plan as planlib
from repro.kernels.octent import ops as oct_ops
from repro.kernels.spconv_gemm import ops as sg_ops
from repro.launch.spconv_serve import ServeEngine, split_plans
from repro.models import minkunet
from repro.runtime import admission, fault, guard
from tests.proptest import random_cloud

CFG = minkunet.MinkUNetConfig(name="minkunet-trace-tiny", in_ch=3, classes=4,
                              stem=8, enc=(8, 16), dec=(16, 8), blocks=1,
                              bm=32)
BUCKET = 96
KERNELS = ("octent_query", "spconv_gemm_fused")
STAGES = ("plan.subm3", "plan.gconv2", "plan.tconv2")


@pytest.fixture(autouse=True)
def _fresh_guard_state():
    fault.uninstall()
    with guard.scoped_health():
        yield
    fault.uninstall()


@functools.lru_cache(maxsize=1)
def _params():
    return minkunet.init_model(CFG, jax.random.key(0))


def _cloud(seed: int, n: int = 80):
    coords, batch, valid = random_cloud(np.random.default_rng(seed), n, 12)
    feats = np.random.default_rng(seed + 1000).standard_normal(
        (n, CFG.in_ch)).astype(np.float32)
    return coords, batch, valid, feats


def _engine() -> ServeEngine:
    queue = admission.AdmissionQueue(buckets=(BUCKET,),
                                     grid_bits=CFG.grid_bits,
                                     batch_bits=CFG.batch_bits)
    return ServeEngine(_params(), CFG, impl="ref", queue=queue, max_batch=2)


def _host_spans(trace_dir) -> list:
    """``(name, start_ns, end_ns, stats)`` of the program's host spans."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats)) for ev in line.events
                       if ev.name.startswith(("serve.", "plan.")))
    return out


def _inside(outer, spans, name=None):
    _, s, e, _ = outer
    return [sp for sp in spans if sp is not outer and s <= sp[1]
            and sp[2] <= e and (name is None or sp[0] == name)]


def test_serve_tick_spans_nest_per_request(tmp_path):
    engine = _engine()
    for k in range(2):                     # compile outside the trace
        engine.submit(f"warm{k}", *_cloud(10 + k))
    engine.step()
    for k in range(2):
        engine.submit(f"r{k}", *_cloud(k))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        results = engine.step()
    finally:
        jax.profiler.stop_trace()
    assert [r.status for r in results] == ["completed"] * 2

    spans = _host_spans(tmp_path)
    tick, = [sp for sp in spans if sp[0] == "serve.tick"]
    assert tick[3]["batch"] == 2 and tick[3]["tick"] == engine.ticks
    assert len(_inside(tick, spans, "serve.admit")) == 1
    assert len(_inside(tick, spans, "serve.dispatch")) == 2
    for rid in ("r0", "r1"):
        mine = [sp for sp in _inside(tick, spans) if sp[3].get("rid") == rid]
        assert sorted(sp[0] for sp in mine) == [
            "serve.build", "serve.fetch", "serve.finish"]
        build = next(sp for sp in mine if sp[0] == "serve.build")
        fetch = next(sp for sp in mine if sp[0] == "serve.fetch")
        assert fetch[1] >= build[2]
        pb, = _inside(build, spans, "plan.build")
        stages = [sp for sp in _inside(pb, spans) if sp[0] in STAGES]
        assert sorted((sp[0], sp[3]["r"]) for sp in stages) == sorted(
            [("plan.subm3", r) for r in range(len(CFG.enc) + 1)]
            + [("plan.gconv2", r + 1) for r in range(len(CFG.enc))]
            + [("plan.tconv2", len(CFG.enc) - 1 - i)
               for i in range(len(CFG.dec))])
        for st in stages:
            kids = {sp[0] for sp in _inside(st, spans)}
            want = {"plan.fingerprint", "plan.search", "plan.tiles"}
            if st[0] == "plan.subm3":
                want.add("plan.check")
            assert kids == want, (st, kids)


def _scopes(text: str) -> set:
    """Scope names in a lowered module's op locations (``plan.py`` and
    other file names excluded)."""
    return {m for m in re.findall(r"(?<![\w.])(?:plan|fwd)\.\w+", text)
            if m != "plan.py"}


def test_device_scopes_reach_op_metadata():
    coords, batch, valid, feats = _cloud(3)
    c, b, v = (jnp.asarray(x) for x in (coords, batch, valid))
    lowered = [
        ("plan.tiles", sg_ops._build_tap_tiles.lower(
            jnp.full((64, 27), -1, jnp.int32), None, bm=32, bo=128,
            schedule=True, binning="counting")),
        ("plan.search", oct_ops.build_query_table.lower(c, b, v,
                                                        max_blocks=80)),
        ("plan.search", mapsearch.build_maps_gconv2.lower(c, b, v)),
        ("plan.fingerprint", planlib._fp_words.lower(jnp.ravel(c))),
    ]
    texts = []
    for scope, low in lowered:
        texts.append(low.as_text(debug_info=True))
        assert scope in _scopes(texts[-1]), scope

    engine = _engine()
    plans = minkunet.build_plans(c, b, v, CFG, cache=engine.cache)
    dyn, treedef, static, skeleton = split_plans(plans)
    fn = engine._executable(skeleton, treedef, static, "ref")
    fwd = fn.lower(_params(), c, b, v, jnp.asarray(feats),
                   dyn).as_text(debug_info=True)
    assert {"fwd.subm3", "fwd.down", "fwd.up", "fwd.bn_relu", "fwd.concat",
            "fwd.head"} <= _scopes(fwd)

    found = set().union(_scopes(fwd), *map(_scopes, texts))
    assert not [s for s in found for k in KERNELS if k in s]


def test_plan_host_syncs_counted_per_fresh_cloud_and_zero_on_repeat():
    coords, batch, valid, _ = _cloud(5)
    c, b, v = (jnp.asarray(x) for x in (coords, batch, valid))
    cache = planlib.PlanCache()
    n_enc, n_dec = len(CFG.enc), len(CFG.dec)
    # Subm3: 3 key arrays fingerprinted + the block-capacity check;
    # Gconv2: 3 key arrays; Tconv2: 4 map arrays + 3 target arrays
    want = 4 * (n_enc + 1) + 3 * n_enc + 7 * n_dec
    with guard.scoped_health() as h:
        first = minkunet.build_plans(c, b, v, CFG, cache=cache)
        assert h.get("plan.host_sync") == want
    with guard.scoped_health() as h:
        again = minkunet.build_plans(c, b, v, CFG, cache=cache)
        assert h.get("plan.host_sync") == 0
    assert again == first
