"""Serving-runtime tests (DESIGN.md §12).

Covers the admission layer (padding-bucket quantization, bounded-queue
backpressure, deadline shedding, strict-policy rejections including the
``oversize`` class, the ``admit`` fault site), the continuous-batching
engine (per-bucket compiled executables, content-addressed search
dedup, per-request fault isolation with bit-identical batchmates, the
``batch`` fault site, the graceful-degradation ladder up to shedding
mode and back down), the guard quarantine lifecycle across cooldown
expiry, the structured health-JSON export, and the ``launch.serve``
sampled-decoding default-key regression.
"""
from __future__ import annotations

import functools
import json
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import plan as planlib, validate
from repro.models import minkunet
from repro.runtime import admission, fault, guard
from tests.proptest import random_cloud

SERVE_CFG = minkunet.MinkUNetConfig(name="minkunet-serve-tiny", in_ch=3,
                                    classes=4, stem=8, enc=(8,), dec=(8,),
                                    blocks=1, bm=32)
BUCKETS = (48, 96)
#: map searches a fresh geometry costs under SERVE_CFG (build_plans:
#: len(enc) Gconv2 + len(enc)+1 Subm3)
SEARCHES_PER_GEOM = 2 * len(SERVE_CFG.enc) + 1


@pytest.fixture(autouse=True)
def _fresh_guard_state():
    """Health counters, quarantine, and capacity hints are process-wide:
    scope them per test so leakage in either direction is impossible."""
    fault.uninstall()
    with guard.scoped_health():
        yield
    fault.uninstall()


@functools.lru_cache(maxsize=1)
def _params():
    return minkunet.init_model(SERVE_CFG, jax.random.key(0))


def _cloud(seed: int, n: int):
    coords, batch, valid = random_cloud(np.random.default_rng(seed), n, 12)
    feats = np.random.default_rng(seed + 1000).standard_normal(
        (n, SERVE_CFG.in_ch)).astype(np.float32)
    return coords, batch, valid, feats


def _engine(**kw):
    from repro.launch.spconv_serve import ServeEngine
    queue = admission.AdmissionQueue(capacity=kw.pop("capacity", 16),
                                     buckets=BUCKETS,
                                     grid_bits=SERVE_CFG.grid_bits,
                                     batch_bits=SERVE_CFG.batch_bits)
    return ServeEngine(_params(), SERVE_CFG, impl="ref", queue=queue,
                       max_batch=kw.pop("max_batch", 4), **kw)


# ---------------------------------------------------------------------------
# Bucket quantization
# ---------------------------------------------------------------------------

def test_bucket_for_picks_smallest_fit():
    assert admission.bucket_for(10, (48, 96)) == 48
    assert admission.bucket_for(48, (48, 96)) == 48
    assert admission.bucket_for(49, (48, 96)) == 96
    assert admission.bucket_for(97, (48, 96)) is None


def test_quantize_compacts_and_pads_deterministically():
    c, b, v, f = _cloud(0, 30)
    v = v.copy()
    v[::3] = False                                  # holes to compact out
    cq, bq, vq, fq, n = admission.quantize_to_bucket(c, b, v, f, 48)
    assert cq.shape == (48, 3) and fq.shape == (48, SERVE_CFG.in_ch)
    assert n == int(v.sum()) and int(vq.sum()) == n
    assert vq[:n].all() and not vq[n:].any()        # compacted to the front
    np.testing.assert_array_equal(cq[:n], c[v])     # keep-first, stable
    assert not cq[n:].any() and not fq[n:].any()    # zero padding
    # fresh allocations of identical content -> byte-identical buffers
    again = admission.quantize_to_bucket(c.copy(), b.copy(), v.copy(),
                                         f.copy(), 48)
    for a, bb in zip((cq, bq, vq, fq), again[:4]):
        np.testing.assert_array_equal(a, bb)


def test_bucket_classes_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_SERVE_BUCKETS", "96,32")
    assert admission.bucket_classes() == (32, 96)   # sorted ascending
    monkeypatch.delenv("REPRO_SERVE_BUCKETS")
    assert admission.bucket_classes() == admission.DEFAULT_BUCKETS


# ---------------------------------------------------------------------------
# Admission queue: backpressure, rejection taxonomy, deadlines, faults
# ---------------------------------------------------------------------------

def _queue(**kw):
    kw.setdefault("buckets", BUCKETS)
    return admission.AdmissionQueue(**kw)


def test_queue_full_backpressure():
    q = _queue(capacity=1)
    c, b, v, f = _cloud(1, 20)
    assert isinstance(q.submit("a", c, b, v, f), admission.Request)
    rej = q.submit("b", c, b, v, f)
    assert isinstance(rej, admission.Rejection)
    assert rej.reason == admission.SHED_QUEUE_FULL and rej.shed
    assert guard.health().get("admit.shed.queue_full") == 1


def test_strict_rejects_invalid_and_oversize():
    q = _queue(capacity=8)
    c, b, v, f = _cloud(2, 20)
    cf = c.astype(np.float32)
    cf[0] = np.nan
    rej = q.submit("nan", cf, b, v, f)
    assert rej.reason == admission.REJECT_INVALID and not rej.shed
    big = _cloud(3, 120)                            # > max(BUCKETS)
    rej = q.submit("big", *big)
    assert rej.reason == admission.REJECT_OVERSIZE
    assert rej.kind == "oversize"
    assert len(q) == 0


def test_repair_policy_truncates_oversize_keep_first():
    q = _queue(capacity=8, policy=validate.REPAIR)
    c, b, v, f = _cloud(4, 120)
    req = q.submit("big", c, b, v, f)
    assert isinstance(req, admission.Request)
    assert req.bucket == 96 and req.n_valid == 96
    np.testing.assert_array_equal(req.coords[:96], c[:96])  # keep-first


def test_deadline_shed_at_dequeue():
    now = [0.0]
    q = _queue(capacity=8, clock=lambda: now[0])
    c, b, v, f = _cloud(5, 20)
    q.submit("slow", c, b, v, f, deadline_s=0.5)
    q.submit("ok", c, b, v, f, deadline_s=100.0)
    now[0] = 1.0
    got, shed = q.take(8, est_service_s=lambda bucket: 0.25)
    assert [r.rid for r in got] == ["ok"]
    assert [(r.rid, r.reason) for r in shed] == \
        [("slow", admission.SHED_DEADLINE)]
    assert guard.health().get("admit.shed.deadline") == 1


def test_admit_fault_transient_admits_persistent_isolates():
    c, b, v, f = _cloud(6, 20)
    q = _queue(capacity=8)
    with fault.inject(fault.FaultPlan(schedule={"admit": [0, 2, 3]})):
        ok = q.submit("survivor", c, b, v, f)     # idx 0 fires, 1 retries
        rej = q.submit("victim", c, b, v, f)      # idx 2 and 3 both fire
    assert isinstance(ok, admission.Request)
    assert rej.reason == admission.ISOLATED_FAULT and not rej.shed
    assert guard.health().get("admit.retry") == 2  # one retry per request
    assert guard.health().get("admit.isolated_fault") == 1
    assert len(q) == 1                            # victim never enqueued


# ---------------------------------------------------------------------------
# Engine: per-bucket executables, dedup, isolation, ladder
# ---------------------------------------------------------------------------

def test_engine_one_executable_per_bucket_and_search_dedup():
    planlib.reset_mapsearch_counter()
    eng = _engine()
    small, big = _cloud(10, 30), _cloud(11, 70)
    for rid, cl in [("s0", small), ("b0", big), ("s1", small), ("b1", big)]:
        eng.submit(rid, *(a.copy() for a in cl))
    results = eng.drain()
    assert [r.status for r in results] == ["completed"] * 4
    # repeats are fresh allocations: content keys dedup them to zero
    # extra searches, and the compile count is the bucket count
    assert planlib.mapsearch_call_count() == 2 * SEARCHES_PER_GEOM
    assert eng.compiled == 2
    assert {r.bucket for r in results} == set(BUCKETS)
    s = eng.stats()
    assert s["completed"] == 4 and s["cache"]["content_hits"] > 0


def test_engine_isolates_victim_batchmates_bit_identical():
    cl_a, cl_b = _cloud(12, 30), _cloud(13, 34)
    clean = _engine()
    clean.submit("a", *cl_a)
    clean.submit("v", *cl_b)
    clean.drain()
    want = {r.rid: r.digest for r in clean.results}
    guard.reset_health()

    eng = _engine()
    # submission 'a' consumes admit idx 0; 'v' consumes 1 and (retry) 2
    with fault.inject(fault.FaultPlan(schedule={"admit": [1, 2]})):
        eng.submit("a", *cl_a)
        eng.submit("v", *cl_b)
        eng.drain()
    by = {r.rid: r for r in eng.results}
    assert by["v"].status == "isolated"
    assert by["v"].reason == admission.ISOLATED_FAULT
    assert by["a"].status == "completed"
    assert by["a"].digest == want["a"]            # batchmate untouched
    assert guard.health().get("serve.isolated") == 1


def test_engine_exec_fault_recovers_bit_identical():
    cl = _cloud(14, 30)
    clean = _engine()
    clean.submit("r", *cl)
    clean.drain()
    want = clean.results[0].digest
    guard.reset_health()

    eng = _engine()
    with fault.inject(fault.FaultPlan(schedule={"gemm": [0]})):
        eng.submit("r", *cl)
        eng.drain()
    r = eng.results[0]
    assert r.status == "completed" and r.digest == want
    assert guard.health().get("retry.ok.gemm") == 1


def test_engine_batch_fault_transient_then_persistent():
    cl = _cloud(15, 30)
    eng = _engine()
    with fault.inject(fault.FaultPlan(schedule={"batch": [0]})):
        eng.submit("t", *cl)                      # idx 0 fires, 1 retries
        eng.drain()
    assert eng.results[0].status == "completed"
    assert guard.health().get("serve.batch_retry") == 1

    eng2 = _engine()
    with fault.inject(fault.FaultPlan(schedule={"batch": [0, 1]})):
        eng2.submit("p", *cl)                     # both attempts fire
        eng2.drain()
    assert eng2.results[0].status == "isolated"
    assert guard.health().get("serve.isolated") == 1


def test_degradation_ladder_climbs_sheds_and_recovers():
    cl = _cloud(16, 30)
    eng = _engine(max_batch=1, recover_after=1)
    for i in range(4):
        eng.submit(f"r{i}", *cl)
    # every batch-assembly attempt faults: each tick isolates its one
    # request and climbs a rung; at the top the queue is shed outright
    with fault.inject(fault.FaultPlan(schedule={"batch": range(40)})):
        eng.drain()
    statuses = [r.status for r in eng.results]
    assert statuses == ["isolated"] * 3 + ["shed"]
    assert eng.results[-1].reason == admission.SHED_OVERLOAD
    h = guard.health()
    assert h.get("serve.degrade.level3") == 1
    assert h.get("admit.shed.overload") == 1
    # the shedding tick itself is fault-free, so it already walked one
    # rung back down; two more healthy ticks recover fully
    assert eng.level == 2
    eng.step()
    eng.step()
    assert eng.level == 0
    assert h.get("serve.degrade.exit") == 3


def test_engine_ledger_matches_health_counters():
    eng = _engine(capacity=2)
    c, b, v, f = _cloud(17, 30)
    eng.submit("a", c, b, v, f)
    eng.submit("late", c, b, v, f, deadline_s=-1.0)
    eng.submit("over", c, b, v, f)                # queue at capacity
    eng.drain()
    s = eng.stats()
    h = guard.health()
    assert s["completed"] == h.get("serve.completed") == 1
    assert s["shed"] == h.get("serve.shed") == 2
    assert s["isolated"] == h.get("serve.isolated") == 0
    assert h.get("admit.shed.queue_full") == 1
    assert h.get("admit.shed.deadline") == 1


# ---------------------------------------------------------------------------
# Quarantine lifecycle across cooldown expiry
# ---------------------------------------------------------------------------

def test_dispatch_quarantine_cooldown_expiry_readmits(monkeypatch):
    monkeypatch.setenv("REPRO_GUARD_COOLDOWN", "2")
    state = {"fail_primary": True, "primary_calls": 0}

    def call(impl):
        if impl == "fast":
            state["primary_calls"] += 1
            if state["fail_primary"]:
                raise fault.InjectedFault("gemm", state["primary_calls"])
        return impl

    run = lambda: guard.dispatch("gemm", "fast", ("ref",), call, key=("k",))
    h = guard.health()

    assert run() == "ref"                         # 2 failures -> quarantine
    assert state["primary_calls"] == 2
    assert h.get("quarantine.enter.gemm") == 1
    state["fail_primary"] = False                 # impl is healthy again...
    assert run() == "ref"                         # ...but still benched
    assert run() == "ref"
    assert state["primary_calls"] == 2            # never tried while benched
    assert h.get("quarantine.skip.gemm") == 2

    assert run() == "fast"                        # cooldown over: re-admitted
    assert state["primary_calls"] == 3
    assert h.get("fallback.served.gemm") == 3

    state["fail_primary"] = True                  # second persistent failure
    assert run() == "ref"                         # -> re-quarantined
    assert h.get("quarantine.enter.gemm") == 2
    assert h.get("fallback.error.gemm") == 4      # two failure pairs


# ---------------------------------------------------------------------------
# Structured health export
# ---------------------------------------------------------------------------

def test_dump_health_json(tmp_path):
    guard.health().note("serve.completed", 3)
    guard.health().note("admit.ok", 3)
    path = tmp_path / "health.json"
    payload = guard.dump_health_json(str(path), meta={"engine": "test"})
    on_disk = json.loads(path.read_text())
    assert on_disk == payload
    assert on_disk["health"]["serve.completed"] == 3
    assert on_disk["meta"]["engine"] == "test"


def test_train_cli_writes_health_json(tmp_path, monkeypatch):
    from repro.launch import train
    path = tmp_path / "train_health.json"
    # main() places JAX's compile cache; keep this worker's where it was
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setattr("sys.argv",
                        ["train", "--arch", "minkunet", "--steps", "1",
                         "--voxels", "64", "--impl", "ref",
                         "--health-json", str(path)])
    train.main()
    payload = json.loads(path.read_text())
    assert payload["meta"]["arch"] == "minkunet"
    assert payload["meta"]["steps"] == 1
    assert isinstance(payload["health"], dict)


# ---------------------------------------------------------------------------
# launch.serve sampled decoding: key=None regression
# ---------------------------------------------------------------------------

def test_generate_nongreedy_defaults_key():
    from repro.launch import serve
    V = 7

    def prefill(params, batch, max_context):
        n = batch["tokens"].shape[0]
        return jnp.zeros((n, V)).at[:, 3].set(1.0), jnp.int32(0)

    def decode_step(params, cache, tok):
        step = cache + 1
        n = tok.shape[0]
        return jnp.zeros((n, 1, V)).at[:, 0, step % V].set(5.0), step

    model = types.SimpleNamespace(prefill=prefill, decode_step=decode_step)
    batch = {"tokens": jnp.zeros((2, 4), jnp.int32)}
    # used to crash in jax.random.split(None) on the first sampled step
    toks, stats = serve.generate(model, {}, batch, max_context=8,
                                 n_steps=4, greedy=False, key=None)
    assert toks.shape == (2, 4)
    assert stats["nonfinite_stops"] == 0
    # deterministic: the default key is fixed
    toks2, _ = serve.generate(model, {}, batch, max_context=8,
                              n_steps=4, greedy=False, key=None)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks2))
