"""SPAC correctness contract (DESIGN.md §2, §14).

The load-bearing regression here is gradient parity: SPAC elision is
*forward-only* lossless. A row that is exactly zero contributes 0 to every
partial sum, so dropping its maps/tiles cannot change the output — but
d(out)/d(feats) of that row is wᵀ·g, not 0, so the backward pass must
differentiate the un-elided geometry math. The pre-fix code replayed the
VJP through the feature-dependent (elided) masks and silently returned
``dfeats = 0`` for exactly-zero rows on every impl; these tests fail on
that code.

Also covered: forward losslessness is *exact* (element-equal, tile grain
and Cin-block grain, including all-dead and single-live-row edge tiles),
``sparsity_stats`` on degenerate clouds, non-multiple shapes through
``sparse_dense_matmul`` (pad-and-slice) and ``block_mask`` (ValueError),
and the fused BN/ReLU epilogue with in-kernel activation-sparsity
emission (§14).
"""
import os

import numpy as np
import jax
import jax.extend
import jax.numpy as jnp
import pytest

from repro.core import plan as planlib
from repro.core import rulebook, sparsity, spconv
from repro.core.spconv import SparseTensor
from repro.kernels.masked_matmul.ops import sparse_dense_matmul
from repro.kernels.spconv_gemm import ops as sg_ops
from tests.proptest import forall, random_cloud

KIMPL = sg_ops.hardware_impl()
BM = 8


def _zero_row_st(rng, n, c, zero_frac, extent=14, batch=2):
    """Cloud whose features mix signs (NOT post-ReLU) with a block of
    exactly-zero rows — the case the elided backward used to silently
    drop."""
    coords, bidx, valid = random_cloud(rng, n, extent=extent, batch=batch)
    feats = rng.standard_normal((n, c)).astype(np.float32)
    zero_rows = rng.random(n) < zero_frac
    feats[zero_rows] = 0.0
    feats[~valid] = 0.0
    st = SparseTensor(jnp.asarray(coords), jnp.asarray(bidx),
                      jnp.asarray(valid), jnp.asarray(feats))
    return st, zero_rows & valid


# ---------------------------------------------------------------------------
# Headline regression: SPAC elision must not zero gradients
# ---------------------------------------------------------------------------

@forall(4)
def test_spac_gradient_parity_kmap_fused(rng):
    """apply_kmap_fused(spac=True) grads == spac=False grads, even though
    the forward elides maps sourcing exactly-zero rows (ref + kernel)."""
    n, cin, cout = 40, 6, 10
    st, zero_rows = _zero_row_st(rng, n, cin, zero_frac=0.5)
    params = spconv.init_conv(jax.random.key(1), 27, cin, cout)
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)
    cot = jnp.asarray(rng.standard_normal((n, cout)).astype(np.float32))

    for impl in dict.fromkeys(("ref", KIMPL)):
        def loss(f, w, spac):
            out = sg_ops.apply_kmap_fused(f, w, plan.kmap, params["b"],
                                          spac=spac, bm=BM, impl=impl)
            return (out * cot).sum()

        df_on, dw_on = jax.grad(loss, (0, 1))(st.feats, params["w"], True)
        df_off, dw_off = jax.grad(loss, (0, 1))(st.feats, params["w"], False)
        # the test must be sensitive: the un-elided grads of zero rows are
        # nonzero (those rows have neighbors, so w^T . g flows back)
        assert float(jnp.abs(df_off[zero_rows]).max()) > 0
        np.testing.assert_allclose(np.asarray(df_on), np.asarray(df_off),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"dfeats mismatch impl={impl}")
        np.testing.assert_allclose(np.asarray(dw_on), np.asarray(dw_off),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"dweights mismatch impl={impl}")


@forall(4)
def test_spac_gradient_parity_plan_execute(rng):
    """plan.execute grads spac on/off agree on every impl, including the
    'xla' path whose forward elides via compact_kmap."""
    n, cin, cout = 40, 6, 10
    st, zero_rows = _zero_row_st(rng, n, cin, zero_frac=0.6)
    params = spconv.init_conv(jax.random.key(2), 27, cin, cout)
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)
    cot = jnp.asarray(rng.standard_normal((n, cout)).astype(np.float32))

    for impl in dict.fromkeys(("xla", "ref", KIMPL)):
        def loss(f, w, spac):
            out = planlib.execute(plan, f, w, params["b"], spac=spac,
                                  impl=impl)
            return (out * cot).sum()

        df_on, dw_on = jax.grad(loss, (0, 1))(st.feats, params["w"], True)
        df_off, dw_off = jax.grad(loss, (0, 1))(st.feats, params["w"], False)
        assert float(jnp.abs(df_off[zero_rows]).max()) > 0
        np.testing.assert_allclose(np.asarray(df_on), np.asarray(df_off),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"dfeats mismatch impl={impl}")
        np.testing.assert_allclose(np.asarray(dw_on), np.asarray(dw_off),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"dweights mismatch impl={impl}")


# ---------------------------------------------------------------------------
# Forward losslessness: exact, at tile AND Cin-block grain
# ---------------------------------------------------------------------------

def _exec_on_off(st, w, plan, *, impl, bk=None):
    on = sg_ops.apply_tiles(st.feats, w, plan.tiles, n_out=plan.n_out,
                            row_nz=sparsity.row_nonzero(st.feats), bk=bk,
                            impl=impl)
    off = sg_ops.apply_tiles(st.feats, w, plan.tiles, n_out=plan.n_out,
                             bk=bk, impl=impl)
    return on, off


@forall(4)
def test_spac_forward_lossless_exact(rng):
    """spac-on output element-equal to spac-off: liveness only skips
    contributions that are exactly zero (tile grain and Cin-block grain —
    c_in=32 with bk=16 exercises per-(tile, block) masks)."""
    n, cin, cout = 48, 32, 8
    st, _ = _zero_row_st(rng, n, cin, zero_frac=0.5)
    # Cin-block-grain deadness: zero the upper half-channels of many rows
    feats = np.array(st.feats)
    feats[rng.random(n) < 0.5, 16:] = 0.0
    st = st.replace_feats(jnp.asarray(feats))
    w = jnp.asarray(rng.standard_normal((27, cin, cout)).astype(np.float32))
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)
    for impl in dict.fromkeys(("ref", KIMPL)):
        on, off = _exec_on_off(st, w, plan, impl=impl, bk=16)
        assert bool((on == off).all()), f"spac-on drifted, impl={impl}"


def test_spac_forward_lossless_edge_tiles():
    """All-rows-zero (every tile dead) and single-live-row edge tiles."""
    rng = np.random.default_rng(7)
    n, cin, cout = 32, 8, 6
    st, _ = _zero_row_st(rng, n, cin, zero_frac=0.0)
    w = jnp.asarray(rng.standard_normal((27, cin, cout)).astype(np.float32))
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)
    for build in ("all_zero", "single_live"):
        feats = np.zeros((n, cin), np.float32)
        if build == "single_live":
            feats[3] = rng.standard_normal(cin).astype(np.float32)
        sti = st.replace_feats(jnp.asarray(feats))
        for impl in dict.fromkeys(("ref", KIMPL)):
            on, off = _exec_on_off(sti, w, plan, impl=impl)
            assert bool((on == off).all()), (build, impl)


def test_spac_block_flag_off_still_lossless():
    """REPRO_SPAC_BLOCK=0 drops to tile grain only — output unchanged."""
    rng = np.random.default_rng(3)
    n, cin, cout = 40, 32, 8
    st, _ = _zero_row_st(rng, n, cin, zero_frac=0.5)
    w = jnp.asarray(rng.standard_normal((27, cin, cout)).astype(np.float32))
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)
    on, off = _exec_on_off(st, w, plan, impl=KIMPL, bk=16)
    os.environ["REPRO_SPAC_BLOCK"] = "0"
    try:
        on2, _ = _exec_on_off(st, w, plan, impl=KIMPL, bk=16)
    finally:
        del os.environ["REPRO_SPAC_BLOCK"]
    assert bool((on == off).all())
    assert bool((on2 == off).all())


# ---------------------------------------------------------------------------
# The liveness refresh short-circuits only where it is exact
# ---------------------------------------------------------------------------

#: case -> (bk, whether the refresh must run in full)
REFRESH_CASES = {
    "no_dead_row": (None, False),
    "one_dead_valid_row": (None, True),
    "every_row_dead": (None, True),
    "dead_padding_only": (None, False),
    "block_grain_live": (16, False),
    "block_grain_dead_block": (16, True),
}


def _refresh_case(case):
    rng = np.random.default_rng(11)
    n, cin, cout = 48, 32, 8
    coords, bidx, valid = random_cloud(rng, n, extent=6, batch=2,
                                       n_valid=36)
    feats = rng.standard_normal((n, cin)).astype(np.float32)
    if case == "one_dead_valid_row":
        feats[5] = 0.0
    elif case == "every_row_dead":
        feats[:] = 0.0
    elif case in ("dead_padding_only", "block_grain_live"):
        feats[~valid] = 0.0
    elif case == "block_grain_dead_block":
        feats[~valid] = 0.0
        feats[1:, 16:] = 0.0          # rows live, upper blocks dead
    st = SparseTensor(jnp.asarray(coords), jnp.asarray(bidx),
                      jnp.asarray(valid), jnp.asarray(feats))
    w = jnp.asarray(rng.standard_normal((27, cin, cout)).astype(np.float32))
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)
    return st, w, plan


@pytest.mark.parametrize("impl", list(dict.fromkeys(("ref", KIMPL))))
@pytest.mark.parametrize("case", list(REFRESH_CASES))
def test_refresh_short_circuit_is_exact(case, impl):
    """apply_tiles with ``src_valid`` is element-equal to the path without
    it, and refresh_liveness gives exactly the flags of tile_liveness /
    tile_block_liveness, whichever branch the check takes."""
    bk, want_full = REFRESH_CASES[case]
    st, w, plan = _refresh_case(case)
    row_nz = sparsity.row_nonzero(st.feats)
    with sparsity.refresh_log() as log:
        got = sg_ops.apply_tiles(st.feats, w, plan.tiles, n_out=plan.n_out,
                                 row_nz=row_nz, bk=bk, impl=impl,
                                 src_valid=st.valid)
    assert [int(f) for f in log] == [int(want_full)]
    want = sg_ops.apply_tiles(st.feats, w, plan.tiles, n_out=plan.n_out,
                              row_nz=row_nz, bk=bk, impl=impl)
    assert bool((got == want).all()), case

    tiles = plan.tiles
    blk_nz = None if bk is None else (
        sparsity.row_block_nonzero(st.feats, bk) & row_nz[:, None])
    n_k = 1 if bk is None else st.feats.shape[1] // bk
    tile_nz, tile_bk_nz = sg_ops.refresh_liveness(
        tiles, row_nz, blk_nz, n_k=n_k, src_valid=st.valid)
    ref_nz = sg_ops.tile_liveness(tiles, row_nz)
    ref_bk = (jnp.repeat(ref_nz[:, None], n_k, axis=1) if blk_nz is None
              else sg_ops.tile_block_liveness(tiles, blk_nz))
    np.testing.assert_array_equal(np.asarray(tile_nz), np.asarray(ref_nz))
    np.testing.assert_array_equal(np.asarray(tile_bk_nz), np.asarray(ref_bk))
    # the full refresh must find something the geometry flags miss in
    # the cases that need it, else the case would not test the branch
    geo = np.asarray(tiles.tile_nz)
    if case == "every_row_dead":
        assert not np.asarray(ref_nz).any() and geo.any()
    if case == "block_grain_dead_block":
        assert (np.asarray(ref_bk) != geo[:, None]).any()


def _eqns(jaxpr, in_cond=False):
    """Every equation of ``jaxpr`` and of the jaxprs in its params, with
    whether it sits inside a ``cond`` branch."""
    for eqn in jaxpr.eqns:
        yield eqn, in_cond
        inner = in_cond or eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub, inner)


def test_refresh_is_a_cond_not_a_select():
    """In a jitted Subm3 ``plan.execute`` with ``src_valid`` the per-slot
    liveness gather sits in a ``cond`` branch alone: nothing outside
    computes it, so no select of both liveness results can exist."""
    st, w, plan = _refresh_case("no_dead_row")
    fn = jax.jit(lambda f, v: planlib.execute(plan, f, w, impl="ref",
                                              src_valid=v))
    jaxpr = jax.make_jaxpr(fn)(st.feats, st.valid).jaxpr
    eqns = list(_eqns(jaxpr))
    assert sum(e.primitive.name == "cond" for e, _ in eqns) == 1

    def bool_gather(e):
        return (e.primitive.name == "gather"
                and e.outvars[0].aval.dtype == jnp.bool_)

    assert [inside for e, inside in eqns if bool_gather(e)] == [True]


# ---------------------------------------------------------------------------
# sparsity_stats degenerate clouds
# ---------------------------------------------------------------------------

def test_sparsity_stats_empty_kmap_reports_zero_elision():
    """An empty kmap elides nothing: map_elision must be 0.0, not 1.0
    (the pre-fix clamp computed 1 - 0/1)."""
    feats = jnp.ones((8, 4))
    kmap = jnp.full((8, 27), -1, jnp.int32)
    stats = sparsity.sparsity_stats(feats, kmap, c_out=4)
    assert float(stats.map_elision) == 0.0
    assert float(stats.macs_dense) == 0.0


def test_sparsity_stats_all_zero_cloud():
    """Degenerate all-zero features: every valid map elides."""
    feats = jnp.zeros((8, 4))
    kmap = jnp.zeros((8, 27), jnp.int32)
    stats = sparsity.sparsity_stats(feats, kmap, c_out=4)
    assert float(stats.map_elision) == 1.0
    assert float(stats.row_sparsity) == 1.0
    assert float(stats.macs_row_elided) == 0.0


# ---------------------------------------------------------------------------
# Non-multiple shapes: pad-and-slice / ValueError, survives python -O
# ---------------------------------------------------------------------------

@forall(4)
def test_sparse_dense_matmul_non_multiple_shapes(rng):
    m, k, n = 130, 70, 50                      # none a multiple of 128
    a = rng.standard_normal((m, k)).astype(np.float32)
    a[rng.random(m) < 0.5] = 0.0               # some skippable tiles
    b = rng.standard_normal((k, n)).astype(np.float32)
    want = a @ b
    for impl in dict.fromkeys(("ref", KIMPL)):
        got = sparse_dense_matmul(jnp.asarray(a), jnp.asarray(b), impl=impl)
        assert got.shape == (m, n)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                                   atol=1e-5)


def test_block_mask_non_multiple_raises_valueerror():
    with pytest.raises(ValueError):
        sparsity.block_mask(jnp.ones((10, 10)), 8, 8)


def test_row_block_nonzero_non_multiple_raises_valueerror():
    with pytest.raises(ValueError):
        sparsity.row_block_nonzero(jnp.ones((4, 10)), 4)


# ---------------------------------------------------------------------------
# Fused BN/ReLU epilogue + in-kernel activation-sparsity emission (§14)
# ---------------------------------------------------------------------------

@forall(4)
def test_fused_epilogue_matches_unfused(rng):
    """subm_conv3 + batch_norm(inference) + relu == the fused epilogue
    path, and the emitted ActSparsity equals a fresh row sweep exactly."""
    n, c = 40, 8
    st, _ = _zero_row_st(rng, n, c, zero_frac=0.4)
    conv = spconv.init_conv(jax.random.key(3), 27, c, c)
    conv = {**conv, "b": jnp.asarray(rng.standard_normal(c), jnp.float32)}
    bn = spconv.init_batchnorm(c)
    bn = {**bn,
          "mean": jnp.asarray(rng.standard_normal(c), jnp.float32),
          "var": jnp.asarray(rng.random(c) + 0.5, jnp.float32),
          "scale": jnp.asarray(rng.random(c) + 0.5, jnp.float32),
          "bias": jnp.asarray(rng.standard_normal(c), jnp.float32)}
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)
    ref = spconv.subm_conv3(st, conv, max_blocks=n, plan=plan, impl="ref")
    ref, _ = spconv.batch_norm(ref, bn, training=False)
    ref = spconv.relu(ref)
    for impl in dict.fromkeys(("xla", "ref", KIMPL)):
        got, act = spconv.subm_conv3_bn_relu(st, conv, bn, max_blocks=n,
                                             plan=plan, impl=impl)
        np.testing.assert_allclose(np.asarray(got.feats),
                                   np.asarray(ref.feats),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"epilogue drift impl={impl}")
        # the in-kernel act must equal a fresh HBM sweep of the output
        want_nz = sparsity.row_nonzero(got.feats)
        assert bool((act.row_nz == want_nz).all()), impl
        assert bool((act.blk_nz.any(-1) == want_nz).all()), impl


def test_fused_epilogue_is_inference_only():
    """Differentiating through the fused epilogue raises instead of
    silently returning elided (wrong) gradients."""
    rng = np.random.default_rng(5)
    n, c = 32, 8
    st, _ = _zero_row_st(rng, n, c, zero_frac=0.2)
    conv = spconv.init_conv(jax.random.key(4), 27, c, c)
    bn = spconv.init_batchnorm(c)
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)

    def loss(f):
        got, _ = spconv.subm_conv3_bn_relu(st.replace_feats(f), conv, bn,
                                           max_blocks=n, plan=plan,
                                           impl="ref")
        return got.feats.sum()

    with pytest.raises(NotImplementedError):
        jax.grad(loss)(st.feats)


@forall(3)
def test_act_threading_matches_fresh_sweep(rng):
    """Feeding the previous layer's emitted ActSparsity into the next
    layer produces the same output as a fresh row_nonzero sweep."""
    n, c = 40, 8
    st, _ = _zero_row_st(rng, n, c, zero_frac=0.3)
    conv = spconv.init_conv(jax.random.key(6), 27, c, c)
    bn = spconv.init_batchnorm(c)
    plan = planlib.subm3_plan(st.coords, st.batch, st.valid, max_blocks=n,
                              bm=BM)
    st1, act = spconv.subm_conv3_bn_relu(st, conv, bn, max_blocks=n,
                                         plan=plan, impl=KIMPL)
    threaded = planlib.execute(plan, st1.feats, conv["w"], conv["b"],
                               act=act, impl=KIMPL)
    fresh = planlib.execute(plan, st1.feats, conv["w"], conv["b"],
                            impl=KIMPL)
    assert bool((threaded == fresh).all())


def test_minkunet_fused_epilogue_matches_unfused():
    """MinkUNet forward with fused_epilogue=True agrees with the default
    path at inference (BN folded per Subm3 block, act threaded)."""
    from repro.data import pointcloud
    from repro.models import minkunet
    rng = np.random.default_rng(0)
    vb = pointcloud.make_batch(rng, "indoor", batch_size=1, max_voxels=256)
    cfg = minkunet.MinkUNetConfig(in_ch=4, classes=5, stem=8, enc=(8, 16),
                                  dec=(8, 8), blocks=1, bm=BM)
    cfg_f = minkunet.MinkUNetConfig(in_ch=4, classes=5, stem=8, enc=(8, 16),
                                    dec=(8, 8), blocks=1, bm=BM,
                                    fused_epilogue=True)
    params = minkunet.init_model(cfg, jax.random.key(0))
    st = SparseTensor(jnp.asarray(vb.coords), jnp.asarray(vb.batch),
                      jnp.asarray(vb.valid),
                      jnp.asarray(rng.standard_normal(
                          (vb.coords.shape[0], 4)).astype(np.float32)))
    base = minkunet.forward(params, st, cfg)
    fused = minkunet.forward(params, st, cfg_f)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base),
                               rtol=1e-4, atol=1e-4)
