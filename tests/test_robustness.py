"""Hardened-runtime tests (DESIGN.md §11).

Covers the whole guard stack: the ingress sanitizer taxonomy and its
policies, degenerate clouds end-to-end through plan build + MinkUNet
forward under every host search impl, overflow-adaptive replanning
(including the gconv3 candidate-budget overflow that used to truncate
silently), the backend fallback chain with quarantine, the training
runner's skip-then-abort escalation ladder, deterministic fault
injection, the chaos bit-identity property on the train demo, and the
serving loop's non-finite-logit guard.
"""
from __future__ import annotations

import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import plan as planlib, spconv, validate
from repro.models import minkunet
from repro.runtime import fault, guard
from tests.proptest import DEGENERATE_KINDS, degenerate_cloud, random_cloud

TINY = minkunet.MinkUNetConfig(name="minkunet-tiny", in_ch=3, classes=4,
                               stem=8, enc=(8,), dec=(8,), blocks=1, bm=32)


@pytest.fixture(autouse=True)
def _fresh_guard_state():
    """Health counters, quarantine, and capacity hints are process-wide:
    scope them per test so leakage in either direction is impossible."""
    fault.uninstall()
    with guard.scoped_health():
        yield
    fault.uninstall()


# ---------------------------------------------------------------------------
# Ingress sanitizer
# ---------------------------------------------------------------------------

def test_sanitize_clean_returns_original_objects():
    coords, batch, valid = random_cloud(np.random.default_rng(0), 32, 8)
    c, b, v, f, rep = validate.sanitize_cloud(coords, batch, valid)
    assert c is coords and b is batch and v is valid and f is None
    assert rep.ok and not rep.changed
    assert all(rep.counts[k] == 0 for k in validate.CLOUD_FAILURE_CLASSES)


def test_sanitize_taxonomy_counts():
    n = 32
    coords, batch, valid = random_cloud(np.random.default_rng(1), n, 8)

    cf = coords.astype(np.float32)
    cf[:2] = np.nan
    c, _, v, _, rep = validate.sanitize_cloud(cf, batch, valid)
    assert rep.counts["nonfinite"] == 2
    assert np.asarray(c).dtype == np.int32
    assert int(np.asarray(v).sum()) == n - 2

    c2 = coords.copy()
    c2[:3] += 10_000_000
    _, _, v, _, rep = validate.sanitize_cloud(c2, batch, valid)
    assert rep.counts["out_of_grid"] == 3
    assert int(np.asarray(v).sum()) == n - 3

    c3 = coords.copy()
    c3[1:3] = c3[0]
    _, _, v, _, rep = validate.sanitize_cloud(c3, batch, valid)
    assert rep.counts["duplicate"] == 2
    va = np.asarray(v)
    assert va[0] and not va[1:3].any()          # keep-first dedup
    # repairs never change shapes — only valid bits flip
    assert va.shape == valid.shape
    assert guard.health().get("validate.duplicate") == 2


def test_sanitize_strict_raises_with_kind():
    coords, batch, valid = random_cloud(np.random.default_rng(2), 16, 8)
    coords[3] = coords[2]
    with pytest.raises(validate.CloudValidationError) as ei:
        validate.sanitize_cloud(coords, batch, valid, policy=validate.STRICT)
    assert ei.value.kind == "duplicate"
    with pytest.raises(validate.CloudValidationError) as ei:
        validate.sanitize_cloud(coords[:, :2], batch, valid)
    assert ei.value.kind == "shape"


def test_degenerate_clouds_end_to_end(monkeypatch):
    """Every degenerate kind must sanitize, plan, and run the full
    MinkUNet forward under every host search impl without crashing."""
    params = minkunet.init_model(TINY, jax.random.key(0))
    n = 16
    for impl in ("ref", "xla", "interpret"):
        monkeypatch.setenv("REPRO_SEARCH_IMPL", impl)
        for kind in DEGENERATE_KINDS:
            rng = np.random.default_rng(3)
            coords, batch, valid = degenerate_cloud(kind, rng, n=n)
            feats = rng.standard_normal((n, TINY.in_ch)).astype(np.float32)
            st, rep = spconv.make_sparse_tensor(coords, batch, valid, feats)
            assert np.asarray(st.coords).dtype == np.int32, (impl, kind)
            plan = planlib.subm3_plan(st.coords, st.batch, st.valid,
                                      max_blocks=n)
            assert plan.kind == "subm3"
            plans = minkunet.build_plans(st.coords, st.batch, st.valid,
                                         TINY, n_max=n)
            logits = np.asarray(minkunet.forward(params, st, TINY,
                                                 plans=plans))
            assert logits.shape == (n, TINY.classes), (impl, kind)
            assert np.isfinite(logits).all(), (impl, kind)
            assert not logits[~np.asarray(st.valid)].any(), (impl, kind)


# ---------------------------------------------------------------------------
# Overflow-adaptive replanning
# ---------------------------------------------------------------------------

def test_with_replan_escalates_and_memoizes():
    calls = []

    def build(cap):
        calls.append(cap)
        if cap < 40:
            raise validate.CapacityOverflow("block_table", "overflow",
                                            needed=40, capacity=cap)
        return f"plan@{cap}"

    key = ("replan-test", 8)
    assert guard.with_replan(build, 8, retries=3, key=key) == "plan@40"
    assert calls == [8, 40]                    # jumps straight to `needed`
    h = guard.health()
    assert h.get("replan.overflow") == 1
    assert h.get("replan.recovered") == 1
    # the escalated capacity is memoized: the next build starts at 40
    calls.clear()
    assert guard.with_replan(build, 8, retries=3, key=key) == "plan@40"
    assert calls == [40]


def test_with_replan_retries_zero_reraises():
    def always_overflow(cap):
        raise validate.CapacityOverflow("block_table", "overflow",
                                        needed=10 * cap, capacity=cap)

    with pytest.raises(validate.CapacityOverflow):
        guard.with_replan(always_overflow, 8, retries=0)
    with pytest.raises(validate.CapacityOverflow):
        guard.with_replan(always_overflow, 8, retries=2)


def test_gconv3_candidate_overflow_raises_eagerly():
    """The mapsearch truncation fix: a single voxel at odd coordinates
    touches 8 downsampled output sites; out_budget=1 used to drop 7 of
    them silently, now it surfaces like the octree block-table limit."""
    c = jnp.ones((1, 3), jnp.int32)
    b = jnp.zeros((1,), jnp.int32)
    v = jnp.ones((1,), bool)
    with pytest.raises(validate.CapacityOverflow, match="overflow") as ei:
        planlib.gconv3_plan(c, b, v)
    assert ei.value.kind == "candidates"
    assert ei.value.needed == 8 and ei.value.capacity == 1
    # enough budget: builds fine, flag concrete-false
    plan = planlib.gconv3_plan(c, b, v, out_budget=8)
    assert not bool(plan.overflow)


def test_gconv3_candidate_overflow_flag_under_jit():
    def build_flag(c, b, v):
        return planlib.gconv3_plan(c, b, v).overflow

    c = jnp.ones((1, 3), jnp.int32)
    b = jnp.zeros((1,), jnp.int32)
    v = jnp.ones((1,), bool)
    assert bool(jax.jit(build_flag)(c, b, v))


# ---------------------------------------------------------------------------
# Backend fallback chain
# ---------------------------------------------------------------------------

def _small_plan_and_operands(search_impl="ref"):
    rng = np.random.default_rng(4)
    coords, batch, valid = random_cloud(rng, 64, 8)
    c, b, v = map(jnp.asarray, (coords, batch, valid))
    plan = planlib.subm3_plan(c, b, v, max_blocks=64,
                              search_impl=search_impl)
    feats = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((27, 8, 8)).astype(np.float32) * 0.1)
    return (c, b, v), plan, feats, w


def test_gemm_fallback_serves_ref_after_quarantine():
    _, plan, feats, w = _small_plan_and_operands()
    want = np.asarray(planlib.execute(plan, feats, w, impl="ref"))
    # two consecutive faults defeat the retry pair -> quarantine + ref
    with fault.inject(fault.FaultPlan(schedule={"gemm": [0, 1]})):
        got = np.asarray(planlib.execute(plan, feats, w, impl="interpret"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    h = guard.health()
    assert h.get("quarantine.enter.gemm") == 1
    assert h.get("fallback.served.gemm.ref") == 1


def test_gemm_transient_fault_recovers_same_impl():
    _, plan, feats, w = _small_plan_and_operands()
    want = np.asarray(planlib.execute(plan, feats, w, impl="ref"))
    with fault.inject(fault.FaultPlan(schedule={"gemm": [0]})):
        got = np.asarray(planlib.execute(plan, feats, w, impl="ref"))
    np.testing.assert_array_equal(got, want)   # same impl retried: bit-exact
    assert guard.health().get("retry.ok.gemm") == 1
    assert guard.health().get("quarantine.enter.gemm") == 0


def test_search_fallback_is_bit_identical():
    (c, b, v), ref_plan, _, _ = _small_plan_and_operands("ref")
    with fault.inject(fault.FaultPlan(schedule={"search": [0, 1]})):
        fb_plan = planlib.subm3_plan(c, b, v, max_blocks=64,
                                     search_impl="interpret")
    for a, bb in zip(jax.tree_util.tree_leaves(ref_plan.kmap),
                     jax.tree_util.tree_leaves(fb_plan.kmap)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))
    assert guard.health().get("fallback.served.search.ref") == 1


def test_dispatch_reraises_real_errors_without_serving_ref():
    """Only an InjectedFault walks the chain: a kernel that fails to
    lower or compile raises at once, and the oracle is never called."""
    calls = []

    def call(impl):
        calls.append(impl)
        if impl == "pallas":
            raise NotImplementedError("Only 2D gather is supported")
        return impl

    with pytest.raises(NotImplementedError, match="2D gather"):
        guard.dispatch("search", "pallas", ("ref",), call, key=("k",))
    assert calls == ["pallas"]
    h = guard.health().snapshot()
    assert not any(k.startswith(("fallback.", "quarantine.")) for k in h), h


def test_fallback_disabled_propagates(monkeypatch):
    monkeypatch.setenv("REPRO_GUARD_FALLBACK", "0")
    _, plan, feats, w = _small_plan_and_operands()
    with fault.inject(fault.FaultPlan(schedule={"gemm": [0]})):
        with pytest.raises(fault.InjectedFault):
            planlib.execute(plan, feats, w, impl="ref")


# ---------------------------------------------------------------------------
# Runner escalation ladder + fault injection
# ---------------------------------------------------------------------------

def _toy_runner(tmp_path, **rc_kw):
    def train_step(state, batch):
        return {"w": state["w"] + batch}, {"loss": jnp.float32(1.0)}

    rc = fault.RunnerConfig(ckpt_dir=str(tmp_path), ckpt_every=2,
                            max_retries_per_step=1, **rc_kw)
    return fault.TrainRunner(rc, train_step, lambda step: jnp.ones(3),
                             {"w": jnp.zeros(3)})


def test_runner_skips_poison_batch_within_budget(tmp_path):
    runner = _toy_runner(tmp_path, max_skipped_batches=1)

    def poison(step):
        if step == 2:
            raise RuntimeError("poison batch")

    losses = runner.run(5, fail_hook=poison)
    assert runner.skipped_batches == 1
    assert len(losses) == 4                    # the skipped step yields none
    assert guard.health().get("runner.skipped_batch") == 1


def test_runner_aborts_when_skip_budget_exhausted(tmp_path):
    runner = _toy_runner(tmp_path, max_skipped_batches=0)

    def poison(step):
        if step == 2:
            raise RuntimeError("poison batch")

    with pytest.raises(RuntimeError, match="skip budget"):
        runner.run(5, fail_hook=poison)


def test_checkpoint_fault_is_retried_and_tolerated(tmp_path):
    runner = _toy_runner(tmp_path, max_skipped_batches=0)
    with fault.inject(fault.FaultPlan(schedule={"checkpoint": [0]})):
        losses = runner.run(3)
    assert len(losses) == 3
    assert runner.ckpt_failures == 1
    assert guard.health().get("runner.ckpt_failure") == 1


def test_faultplan_rate_mode_is_deterministic():
    mk = lambda seed: fault.FaultPlan(rate=0.3, seed=seed, sites=("plan",))  # noqa: E731
    f1 = [mk(7).fires("plan") for _ in range(1)]  # rebuilt per call: index 0
    p1, p2 = mk(7), mk(7)
    seq1 = [p1.fires("plan") for _ in range(64)]
    seq2 = [p2.fires("plan") for _ in range(64)]
    assert seq1 == seq2                        # same seed: same fire pattern
    assert any(seq1) and not all(seq1)
    assert p1.fired["plan"] == [i for i, hit in enumerate(seq1) if hit]
    p3 = fault.FaultPlan(rate=0.3, seed=8, sites=("plan",))
    assert [p3.fires("plan") for _ in range(64)] != seq1
    assert f1 in ([True], [False])             # scalar sanity


# ---------------------------------------------------------------------------
# Chaos bit-identity on the train demo
# ---------------------------------------------------------------------------

def test_chaos_demo_is_bit_identical():
    from repro.launch.train import run_spconv_demo
    clean = run_spconv_demo(steps=2, voxels=96, impl="ref")
    guard.reset_health()
    plan = fault.FaultPlan(schedule={"search": [1], "gemm": [0], "plan": [4],
                                     "fingerprint": [2], "checkpoint": [1]})
    chaos = run_spconv_demo(steps=2, voxels=96, impl="ref", faults=plan,
                            verify_cache=True)
    assert sorted(plan.fired) == sorted(fault.TRAIN_FAULT_SITES)
    assert chaos["state_digest"] == clean["state_digest"]
    assert chaos["recoveries"] >= 1
    assert chaos["skipped_batches"] == 0       # recovery is never lossy


def test_demo_replans_through_starved_block_table():
    from repro.launch.train import run_spconv_demo
    clean = run_spconv_demo(steps=2, voxels=96, impl="ref")
    guard.reset_health()
    tight = run_spconv_demo(steps=2, voxels=96, impl="ref", max_blocks=4)
    assert tight["state_digest"] == clean["state_digest"]
    assert tight["health"].get("replan.overflow", 0) >= 1
    assert tight["health"].get("replan.recovered", 0) >= 1


# ---------------------------------------------------------------------------
# Serving non-finite guard
# ---------------------------------------------------------------------------

def test_serve_freezes_nonfinite_sequences():
    from repro.launch import serve
    V = 7

    def prefill(params, batch, max_context):
        n = batch["tokens"].shape[0]
        return jnp.zeros((n, V)).at[:, 3].set(1.0), jnp.int32(0)

    def decode_step(params, cache, tok):
        step = cache + 1
        n = tok.shape[0]
        logits = jnp.zeros((n, 1, V)).at[:, 0, step % V].set(1.0)
        # sequence 0's activations blow up from decode step 2 on
        logits = logits.at[0].set(jnp.where(step >= 2, jnp.nan, logits[0]))
        return logits, step

    model = types.SimpleNamespace(prefill=prefill, decode_step=decode_step)
    batch = {"tokens": jnp.zeros((2, 4), jnp.int32)}
    toks, stats = serve.generate(model, None, batch, max_context=16,
                                 n_steps=5)
    toks = np.asarray(toks)
    assert stats["nonfinite_stops"] == 1
    assert guard.health().get("serve.nonfinite_stops") == 1
    assert np.isfinite(toks).all() and (toks >= 0).all()
    assert (toks[0, 2:] == toks[0, 1]).all()   # frozen at last good token
    assert len(set(toks[1].tolist())) > 1      # healthy seq kept decoding
