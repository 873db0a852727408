"""Cross-step caching subsystem: content keys, tiers, invalidation.

Covers the DESIGN.md §10 contract:

  * content-addressed hits across donated/re-allocated identical
    coordinate arrays (identity keys alone would miss every step);
  * a single-voxel perturbation misses (and flips ~half the fingerprint);
  * identity remains the fast path (no fingerprint work on the same
    objects) and the only path under jit tracing;
  * plan eviction under capacity leaves the pinned tier resident — a
    rebuild fetches the stage-1 QueryTable back from the PinnedStore;
  * mesh-change invalidation (§9 fingerprint) still rebuilds on
    identical content;
  * fingerprint collisions are detectable (verify=True) and observable;
  * the end-to-end acceptance loop: a two-step launch/train.py MinkUNet
    run over an identical re-allocated cloud performs map search exactly
    once per distinct cloud, with one compiled step function.
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import plan as planlib
from repro.core import spconv
from repro.core.spconv import SparseTensor
from repro.runtime import feature_cache
from tests.proptest import forall, random_cloud

BM = 8


def _cloud(rng, n=32, extent=14, batch=2):
    coords, bidx, valid = random_cloud(rng, n, extent=extent, batch=batch)
    return coords, bidx, valid


def _as_jnp(*arrays):
    """Freshly allocated device buffers (new objects, same content)."""
    return tuple(jnp.asarray(np.array(a)) for a in arrays)


def _fresh_cache(**kw):
    kw.setdefault("pinned", feature_cache.PinnedStore())
    return planlib.PlanCache(**kw)


# ---------------------------------------------------------------------------
# Content keys
# ---------------------------------------------------------------------------

@forall(6)
def test_content_hit_across_reallocated_arrays(rng):
    """The cross-step property: same bytes, new buffers, same plan."""
    coords, bidx, valid = _cloud(rng)
    cache = _fresh_cache()
    planlib.reset_mapsearch_counter()
    p1 = planlib.subm3_plan(*_as_jnp(coords, bidx, valid), max_blocks=32,
                            bm=BM, search_impl="ref", cache=cache)
    p2 = planlib.subm3_plan(*_as_jnp(coords, bidx, valid), max_blocks=32,
                            bm=BM, search_impl="ref", cache=cache)
    assert p2 is p1
    assert cache.content_hits == 1 and cache.id_hits == 0
    assert planlib.mapsearch_call_count() == 1
    # the new ids are now aliased: a third lookup on the *same* objects
    # takes the identity fast path
    arrays = _as_jnp(coords, bidx, valid)
    p3 = planlib.subm3_plan(*arrays, max_blocks=32, bm=BM,
                            search_impl="ref", cache=cache)
    p4 = planlib.subm3_plan(*arrays, max_blocks=32, bm=BM,
                            search_impl="ref", cache=cache)
    assert p3 is p1 and p4 is p1
    assert cache.id_hits == 1 and cache.content_hits == 2
    assert planlib.mapsearch_call_count() == 1


@forall(6)
def test_content_miss_on_single_voxel_perturbation(rng):
    coords, bidx, valid = _cloud(rng)
    cache = _fresh_cache()
    p1 = planlib.subm3_plan(*_as_jnp(coords, bidx, valid), max_blocks=32,
                            bm=BM, search_impl="ref", cache=cache)
    moved = np.array(coords)
    moved[int(rng.integers(0, len(moved))), int(rng.integers(0, 3))] += 1
    p2 = planlib.subm3_plan(*_as_jnp(moved, bidx, valid), max_blocks=32,
                            bm=BM, search_impl="ref", cache=cache)
    assert p2 is not p1
    assert cache.misses == 2 and cache.hits == 0


def test_fingerprint_is_order_sensitive_and_diffuse():
    """A permuted voxel list is a different rulebook — the fingerprint
    must distinguish it; a one-element change must flip many bits."""
    rng = np.random.default_rng(0)
    coords = rng.integers(0, 64, size=(64, 3)).astype(np.int32)
    fp = planlib.array_fingerprint(jnp.asarray(coords))
    fp_perm = planlib.array_fingerprint(jnp.asarray(coords[::-1].copy()))
    assert fp != fp_perm
    bumped = coords.copy()
    bumped[17, 1] += 1
    fp_bump = planlib.array_fingerprint(jnp.asarray(bumped))
    flipped = sum(bin(a ^ b).count("1")
                  for a, b in zip(fp[2:], fp_bump[2:]))
    assert flipped > 24, f"only {flipped}/96 fingerprint bits flipped"
    # identical content, separately allocated -> identical fingerprint
    assert planlib.array_fingerprint(jnp.asarray(coords.copy())) == fp


def test_tracers_fall_back_to_identity_only():
    """Under jit, key arrays are tracers: no fingerprint, no content
    entry — and within one trace the identity path still dedups."""
    assert planlib.array_fingerprint(jnp.arange(4)) is not None

    rng = np.random.default_rng(1)
    coords, bidx, valid = _cloud(rng)
    cache = _fresh_cache()
    planlib.reset_mapsearch_counter()

    @jax.jit
    def build_twice(c, b, v):
        p1 = planlib.subm3_plan(c, b, v, max_blocks=32, bm=BM,
                                search_impl="ref", cache=cache)
        p2 = planlib.subm3_plan(c, b, v, max_blocks=32, bm=BM,
                                search_impl="ref", cache=cache)
        return p1.kmap, p2.kmap

    k1, k2 = build_twice(*_as_jnp(coords, bidx, valid))
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
    assert planlib.mapsearch_call_count() == 1
    assert cache.id_hits == 1 and cache.content_hits == 0


def test_float_key_arrays_refuse_content_addressing():
    assert planlib.array_fingerprint(jnp.ones((4,), jnp.float32)) is None


def test_int64_high_words_are_hashed_not_truncated():
    """Wide integers hash every 32-bit word: values equal mod 2^32 must
    not collide systematically."""
    import pytest
    prev = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", True)
        lo = jnp.asarray(np.array([1, 2, 3, 4], np.int64))
        if lo.dtype != jnp.int64:
            pytest.skip("x64 unavailable on this host")
        hi = jnp.asarray(np.array([1 + (1 << 32), 2, 3, 4], np.int64))
        fa = planlib.array_fingerprint(lo)
        fb = planlib.array_fingerprint(hi)
    finally:
        jax.config.update("jax_enable_x64", prev)
    assert fa is not None and fb is not None
    assert fa != fb


# ---------------------------------------------------------------------------
# Non-uniform tiers: eviction vs the pinned store
# ---------------------------------------------------------------------------

def test_eviction_under_capacity_keeps_pinned_tier_resident():
    """The §10 decoupling: plans churn (count-bounded FIFO) while the
    small search structures stay pinned (byte-bounded store) — a rebuild
    of evicted geometry fetches stage 1 back instead of rebuilding it."""
    rng = np.random.default_rng(2)
    a = _as_jnp(*_cloud(rng))
    b = _as_jnp(*_cloud(rng))
    store = feature_cache.PinnedStore()
    cache = planlib.PlanCache(capacity=1, pinned=store)

    pa = planlib.subm3_plan(*a, max_blocks=32, bm=BM, search_impl="ref",
                            cache=cache)
    planlib.subm3_plan(*b, max_blocks=32, bm=BM, search_impl="ref",
                       cache=cache)
    assert len(cache) == 1                      # plan A evicted ...
    assert len(store) == 2                      # ... its table is not
    resident = store.resident_bytes()
    assert resident > 0

    hits_before = store.hits
    pa2 = planlib.subm3_plan(*a, max_blocks=32, bm=BM, search_impl="ref",
                             cache=cache)
    assert pa2 is not pa                        # the plan did rebuild
    assert store.hits == hits_before + 1        # from the pinned table
    assert store.resident_bytes() == resident   # nothing re-pinned
    np.testing.assert_array_equal(np.asarray(pa2.kmap), np.asarray(pa.kmap))


def test_pinned_store_byte_capacity_and_residency_split():
    """Store capacity is bytes, not entries; plan residency reports the
    pinned tier as the small one."""
    rng = np.random.default_rng(3)
    a = _as_jnp(*_cloud(rng))
    probe_store = feature_cache.PinnedStore()
    probe = planlib.PlanCache(pinned=probe_store)
    plan = planlib.subm3_plan(*a, max_blocks=32, bm=BM, search_impl="ref",
                              cache=probe)
    entry_bytes = probe_store.resident_bytes()
    assert entry_bytes > 0

    tiny = feature_cache.PinnedStore(capacity_bytes=entry_bytes)
    cache = planlib.PlanCache(pinned=tiny)
    for arrays in (a, _as_jnp(*_cloud(rng))):
        planlib.subm3_plan(*arrays, max_blocks=32, bm=BM,
                           search_impl="ref", cache=cache)
    assert len(tiny) == 1 and tiny.evictions == 1
    assert tiny.resident_bytes() <= tiny.capacity_bytes

    res = plan.residency
    assert 0 < res["pinned"] < res["cached"]
    assert res["stream"] == 0


# ---------------------------------------------------------------------------
# Invalidation
# ---------------------------------------------------------------------------

def test_mesh_change_invalidates_identical_content():
    """Same bytes under a different mesh must rebuild (§9 fingerprint in
    every key) — and return to the off-mesh entry afterwards."""
    from jax.sharding import Mesh
    from jax import set_mesh

    rng = np.random.default_rng(4)
    coords, bidx, valid = _cloud(rng)
    cache = _fresh_cache()
    p_off = planlib.subm3_plan(*_as_jnp(coords, bidx, valid), max_blocks=32,
                               bm=BM, search_impl="ref", cache=cache)
    dev = np.array(jax.devices()[:1])
    with set_mesh(Mesh(dev.reshape(1), ("data",))):
        p_mesh = planlib.subm3_plan(*_as_jnp(coords, bidx, valid),
                                    max_blocks=32, bm=BM, search_impl="ref",
                                    cache=cache)
        assert p_mesh is not p_off and cache.misses == 2
    p_back = planlib.subm3_plan(*_as_jnp(coords, bidx, valid), max_blocks=32,
                                bm=BM, search_impl="ref", cache=cache)
    assert p_back is p_off and cache.content_hits == 1


def test_collision_detected_and_rebuilt_with_verify(monkeypatch):
    """verify=True compares arrays on content hits: a forced fingerprint
    collision is counted and rebuilt, never served stale — at *both*
    levels. The PinnedStore is keyed by the same fingerprint, so the
    rebuild must not fetch the colliding geometry's QueryTable either:
    the rebuilt plan's kmap has to match the cacheless ground truth."""
    rng = np.random.default_rng(5)
    a = _as_jnp(*_cloud(rng))
    b = _as_jnp(*_cloud(rng))
    truth_b = planlib.subm3_plan(*b, max_blocks=32, bm=BM,
                                 search_impl="ref")

    constant = planlib.array_fingerprint(a[0])
    monkeypatch.setattr(planlib, "array_fingerprint", lambda x: constant)

    cache = _fresh_cache(verify=True)
    pa = planlib.subm3_plan(*a, max_blocks=32, bm=BM, search_impl="ref",
                            cache=cache)
    pb = planlib.subm3_plan(*b, max_blocks=32, bm=BM, search_impl="ref",
                            cache=cache)
    assert pb is not pa
    assert cache.collisions == 1 and cache.misses == 2
    assert cache.pinned.collisions == 1         # store dropped A's table
    np.testing.assert_array_equal(np.asarray(pb.kmap),
                                  np.asarray(truth_b.kmap))
    # without verify the same stub would have (wrongly) content-hit:
    # prove the counter is the only thing standing between the two
    relaxed = _fresh_cache(verify=False)
    pa2 = planlib.subm3_plan(*a, max_blocks=32, bm=BM, search_impl="ref",
                             cache=relaxed)
    pb2 = planlib.subm3_plan(*b, max_blocks=32, bm=BM, search_impl="ref",
                             cache=relaxed)
    assert pb2 is pa2 and relaxed.content_hits == 1


def test_verify_survives_donated_anchor_buffers():
    """verify=True must not crash (or serve unverified) when every
    anchored alias was donated/deleted: the entry rebuilds, and the
    rebuild re-anchors live arrays so the next hit verifies again."""
    rng = np.random.default_rng(9)
    coords, bidx, valid = _cloud(rng)
    cache = _fresh_cache(verify=True)
    a = _as_jnp(coords, bidx, valid)
    pa = planlib.subm3_plan(*a, max_blocks=32, bm=BM, search_impl="ref",
                            cache=cache)
    for arr in a:                       # simulate jit buffer donation
        arr.delete()
    b = _as_jnp(coords, bidx, valid)
    pb = planlib.subm3_plan(*b, max_blocks=32, bm=BM, search_impl="ref",
                            cache=cache)
    assert pb is not pa                 # unverifiable -> rebuilt
    assert cache.collisions == 0        # not misreported as a collision
    np.testing.assert_array_equal(np.asarray(pb.kmap), np.asarray(pa.kmap))
    # live anchors again: the next re-allocated lookup content-hits
    pc = planlib.subm3_plan(*_as_jnp(coords, bidx, valid), max_blocks=32,
                            bm=BM, search_impl="ref", cache=cache)
    assert pc is pb and cache.content_hits == 1


def test_verifying_reader_refuses_anchorless_pinned_entries():
    """An entry pinned by a non-verifying cache carries no anchor; a
    verify=True cache sharing the store must rebuild (and re-pin with an
    anchor) instead of consuming it unverified."""
    rng = np.random.default_rng(10)
    arrays = _as_jnp(*_cloud(rng))
    store = feature_cache.PinnedStore()
    planlib.subm3_plan(*arrays, max_blocks=32, bm=BM, search_impl="ref",
                       cache=planlib.PlanCache(pinned=store))
    assert len(store) == 1

    strict = planlib.PlanCache(verify=True, pinned=store)
    misses_before = store.misses
    planlib.subm3_plan(*_as_jnp(*_cloud(np.random.default_rng(10))),
                       max_blocks=32, bm=BM, search_impl="ref",
                       cache=strict)
    assert store.misses == misses_before + 1    # anchorless entry refused
    assert len(store) == 1                      # re-pinned, now anchored
    hits_before = store.hits
    # the strict cache's plan is cached; evict it to force a store read
    strict2 = planlib.PlanCache(verify=True, pinned=store)
    planlib.subm3_plan(*_as_jnp(*_cloud(np.random.default_rng(10))),
                       max_blocks=32, bm=BM, search_impl="ref",
                       cache=strict2)
    assert store.hits == hits_before + 1        # anchored entry verifies


def test_content_flag_and_env_opt_out(monkeypatch):
    rng = np.random.default_rng(6)
    coords, bidx, valid = _cloud(rng)
    cache = _fresh_cache(content=False)
    planlib.subm3_plan(*_as_jnp(coords, bidx, valid), max_blocks=32, bm=BM,
                       search_impl="ref", cache=cache)
    planlib.subm3_plan(*_as_jnp(coords, bidx, valid), max_blocks=32, bm=BM,
                       search_impl="ref", cache=cache)
    assert cache.misses == 2 and cache.content_hits == 0
    monkeypatch.setenv("REPRO_PLANCACHE_CONTENT", "0")
    assert planlib.PlanCache().content is False
    monkeypatch.delenv("REPRO_PLANCACHE_CONTENT")
    assert planlib.PlanCache().content is True


# ---------------------------------------------------------------------------
# End to end: prebuilt plans + the two-step training loop
# ---------------------------------------------------------------------------

def test_forward_with_prebuilt_plans_matches_cache_path():
    from repro.data import pointcloud
    from repro.models import minkunet

    cfg = minkunet.MinkUNetConfig(stem=8, enc=(8, 16), dec=(16, 8),
                                  classes=4, blocks=2)
    params = minkunet.init_model(cfg, jax.random.key(0))
    rng = np.random.default_rng(7)
    vb = pointcloud.make_batch(rng, "indoor", batch_size=1, max_voxels=128)
    st = SparseTensor(jnp.asarray(vb.coords), jnp.asarray(vb.batch),
                      jnp.asarray(vb.valid), jnp.asarray(vb.feats))
    cache = _fresh_cache()
    plans = minkunet.build_plans(st.coords, st.batch, st.valid, cfg,
                                 cache=cache)
    planlib.reset_mapsearch_counter()
    with_plans = minkunet.forward(params, st, cfg, plans=plans, impl="ref")
    assert planlib.mapsearch_call_count() == 0      # plans prebuilt
    ref = minkunet.forward(params, st, cfg, impl="ref")
    np.testing.assert_allclose(np.asarray(with_plans), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_two_step_train_loop_searches_once():
    """The ISSUE-5 acceptance criterion, as run by CI: two train steps
    over an identical re-allocated cloud, map search exactly once per
    distinct cloud, one compiled step function, content hits observed."""
    from repro.launch.train import run_spconv_demo

    res = run_spconv_demo(steps=2, voxels=96, impl="ref")
    assert res["mapsearch_calls"] == res["searches_per_cloud"]
    assert res["compiled_steps"] == 1
    assert res["cache"]["content_hits"] > 0
    assert all(np.isfinite(l) for l in res["losses"])

    # a genuinely different cloud must still pay its own searches
    res2 = run_spconv_demo(steps=2, voxels=96, impl="ref", replay=False)
    assert res2["mapsearch_calls"] == 2 * res2["searches_per_cloud"]
    assert res2["compiled_steps"] == 2


def test_gconv_and_tconv_plans_content_hit_via_minkunet_cache():
    """build_plans over re-allocated arrays: every layer type hits —
    total searches stay at one cloud's worth."""
    from repro.data import pointcloud
    from repro.models import minkunet

    cfg = minkunet.MinkUNetConfig(stem=8, enc=(8, 16), dec=(16, 8),
                                  classes=4, blocks=1)
    rng = np.random.default_rng(8)
    vb = pointcloud.make_batch(rng, "indoor", batch_size=1, max_voxels=96)
    cache = _fresh_cache()
    planlib.reset_mapsearch_counter()
    p1 = minkunet.build_plans(*_as_jnp(vb.coords, vb.batch, vb.valid), cfg,
                              cache=cache)
    searches = planlib.mapsearch_call_count()
    assert searches == 2 * len(cfg.enc) + 1
    p2 = minkunet.build_plans(*_as_jnp(vb.coords, vb.batch, vb.valid), cfg,
                              cache=cache)
    assert planlib.mapsearch_call_count() == searches
    for part1, part2 in zip(p1, p2):
        for a, b in zip(part1, part2):
            assert a is b                      # the same plan objects
