"""SECOND (OpenPCDet ``second.yaml``) on the served path: anisotropic
strided maps against brute force, HeightCompression's channel order, a
tiny preset through ``ServeEngine`` against the plain float32 reference,
and MinkUNet's served answers unchanged by the engine's model interface."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import plan as planlib
from repro.core.spconv import SparseTensor
from repro.launch.spconv_serve import ServeEngine, merge_plans, split_plans
from repro.models import minkunet, second
from repro.models import second_reference as ref
from repro.runtime import admission, guard

#: second.yaml's layer structure at narrow widths and a 32 x 32 x 41 grid
TINY = second.SECONDConfig(
    name="second-tiny", sparse_shape=(32, 32, 41), stem=(8, 8),
    stages=((8, (3, 3, 3), (2, 2, 2), (1, 1, 1), 1),
            (16, (3, 3, 3), (2, 2, 2), (1, 1, 1), 1),
            (16, (3, 3, 3), (2, 2, 2), (1, 1, 0), 1),
            (16, (1, 1, 3), (1, 1, 2), (0, 0, 0), 0)),
    bev_layers=(1, 1), bev_filters=(16, 32), up_filters=(16, 16))
ROWS = 1024


def surface(seed, n_side=32):
    """A bumpy ground sheet with a box on it: the shape of a sweep, so
    that every resolution holds fewer sites than the one before."""
    rng = np.random.default_rng(seed)
    x, y = np.meshgrid(np.arange(n_side), np.arange(n_side), indexing="ij")
    keep = rng.random(x.shape) < 0.6
    z = 12 + (x + 2 * y) // 16 + rng.integers(0, 2, x.shape)
    c = np.stack([x[keep], y[keep], z[keep]], 1)
    box = np.stack(np.meshgrid(np.arange(20, 24), np.arange(4, 8),
                               np.arange(14, 30), indexing="ij"), -1)
    c = np.unique(np.concatenate([c, box.reshape(-1, 3)]), axis=0)
    return c.astype(np.int32), rng.normal(size=(c.shape[0], 4)).astype(
        np.float32)


def padded(c, f, rows=ROWS):
    n = c.shape[0]
    out = (np.zeros((rows, 3), np.int32), np.zeros((rows,), np.int32),
           np.zeros((rows,), bool), np.zeros((rows, f.shape[1]), np.float32))
    out[0][:n], out[2][:n], out[3][:n] = c, True, f
    return out


def random_bn(params, seed):
    """BatchNorm statistics away from identity, so that a wrong eps, mean
    or variance shows."""
    keys = iter(jax.random.split(jax.random.key(seed), 4096))

    def visit(node):
        if isinstance(node, dict) and set(node) == {"scale", "bias", "mean",
                                                    "var"}:
            n = node["scale"].shape
            return {"scale": jax.random.uniform(next(keys), n, minval=0.8,
                                                maxval=1.2),
                    "bias": 0.1 * jax.random.normal(next(keys), n),
                    "mean": 0.1 * jax.random.normal(next(keys), n),
                    "var": jax.random.uniform(next(keys), n, minval=0.8,
                                              maxval=1.2)}
        if isinstance(node, dict):
            return {k: visit(v) for k, v in node.items()}
        return node
    return visit(params)


def engine_for(params, cfg, rows=ROWS):
    queue = admission.AdmissionQueue(buckets=(rows,), grid_bits=cfg.grid_bits,
                                     batch_bits=cfg.batch_bits)
    return ServeEngine(params, cfg, impl="ref", queue=queue, max_batch=2)


def serve_one(engine, arrays):
    engine.submit("r", *arrays, deadline_s=600.0)
    (res,) = engine.step()
    assert res.status == "completed", res
    return res.logits


@pytest.mark.parametrize("kernel,stride,padding,extent", [
    ((3, 3, 3), (2, 2, 2), (1, 1, 1), (16, 12, 9)),      # conv2 / conv3
    ((3, 3, 3), (2, 2, 2), (1, 1, 0), (16, 12, 11)),     # conv4: z p0
    ((1, 1, 3), (1, 1, 2), (0, 0, 0), (16, 12, 5)),      # conv_out
], ids=["k3s2p1", "k3s2p110", "z-only"])
def test_strided_maps_match_brute_force(kernel, stride, padding, extent):
    rng = np.random.default_rng(sum(padding) + kernel[0])
    pts = np.unique(np.concatenate([
        rng.integers(0, extent, (60, 3)),
        [np.array(extent) - 1], np.zeros((1, 3), int)]), axis=0)
    arrays = padded(pts.astype(np.int32),
                    np.zeros((pts.shape[0], 1), np.float32), rows=128)
    c, b, v = (jnp.asarray(x) for x in arrays[:3])
    plan = planlib.gconv3_plan(c, b, v, kernel=kernel, stride=stride,
                               padding=padding, extent=extent, out_budget=512)
    want_out, want_map, hi = ref.strided_map(pts, extent, kernel, stride,
                                             padding)
    ok = np.asarray(plan.out_valid)
    out = np.asarray(plan.out_coords)[ok]
    assert plan.sites == len(want_out) == ok.sum()
    assert np.all(out >= 0) and np.all(out < np.asarray(hi))
    order = np.lexsort(out.T[::-1])
    np.testing.assert_array_equal(out[order], want_out)
    np.testing.assert_array_equal(np.asarray(plan.kmap)[ok][order], want_map)
    # the crop bites: without the extent some outputs lie past it
    uncropped = planlib.gconv3_plan(c, b, v, kernel=kernel, stride=stride,
                                    padding=padding, out_budget=512)
    assert int(np.asarray(uncropped.out_valid).sum()) > len(want_out)


def test_height_compression_is_channel_major():
    cfg = TINY
    w, h, d = second.extents(cfg)[-1]
    coords = np.array([[1, 2, 0], [1, 2, 1], [3, 0, 1], [0, 0, 0]], np.int32)
    feats = np.arange(4 * 3, dtype=np.float32).reshape(4, 3) + 1
    valid = np.array([True, True, True, False])
    bev = np.asarray(second.to_bev(SparseTensor(
        jnp.asarray(coords), jnp.zeros(4, jnp.int32), jnp.asarray(valid),
        jnp.asarray(feats)), cfg))[0]
    assert bev.shape == (h, w, 3 * d) == (4, 4, 6)
    want = np.zeros((3, d, h, w), np.float32)       # (C, D, H, W)
    for (x, y, z), f in zip(coords[valid], feats[valid]):
        want[:, z, y, x] = f
    np.testing.assert_array_equal(bev, want.reshape(3 * d, h, w)
                                  .transpose(1, 2, 0))


def test_height_compression_keeps_clouds_apart():
    # two clouds at the same coordinates: each gets its own map
    coords = np.array([[1, 2, 0], [3, 0, 1], [1, 2, 0], [0, 3, 1]], np.int32)
    batch = np.array([0, 0, 1, 1], np.int32)
    feats = np.arange(4 * 3, dtype=np.float32).reshape(4, 3) + 1
    st = SparseTensor(jnp.asarray(coords), jnp.asarray(batch),
                      jnp.ones(4, bool), jnp.asarray(feats))
    both = np.asarray(second.to_bev(st, TINY, n_batch=2))
    for b in range(2):
        one = SparseTensor(jnp.asarray(coords), jnp.zeros(4, jnp.int32),
                           jnp.asarray(batch == b), jnp.asarray(feats))
        np.testing.assert_array_equal(both[b],
                                      np.asarray(second.to_bev(one, TINY))[0])
    assert both.sum() == feats.sum()


def test_engine_serves_second_as_the_reference():
    params = jax.jit(lambda k: random_bn(second.init_model(TINY, k), 4))(
        jax.random.key(3))
    c, f = surface(5)
    engine = engine_for(params, TINY)
    s0 = planlib.mapsearch_call_count()
    with guard.scoped_health() as h:
        got = serve_one(engine, padded(c, f))
        sites = h.get("serve.bev_sites")
    assert planlib.mapsearch_call_count() - s0 == 8      # 4 subm + 4 strided
    want = ref.forward(params, c, f, TINY)
    assert got.shape == want.shape == (16, 72)
    scale = np.abs(want).max()
    err = np.abs(got - want).max() / scale
    assert err < 1e-5, err
    # the check is tight enough for a bf16 path to fail it
    bf16 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    low = ref.forward(bf16, c, f.astype(jnp.bfloat16).astype(np.float32),
                      TINY)
    assert np.abs(low - want).max() / scale > 100 * 1e-5
    # the BEV counter is the last resolution's sites
    out, _, _ = ref.strided_map(*_last_input(c, TINY), *TINY.stages[-1][1:4])
    assert sites == len(out) > 0


def test_bev_sites_counted_for_a_cached_geometry():
    # a repeated geometry is answered from the PlanCache with no search
    # and no compile, and its BEV sites are still counted
    params = jax.jit(lambda k: second.init_model(TINY, k))(jax.random.key(4))
    c, f = surface(7)
    engine = engine_for(params, TINY)
    with guard.scoped_health() as h:
        first = serve_one(engine, padded(c, f))
        sites = h.get("serve.bev_sites")
        s0 = planlib.mapsearch_call_count()
        again = serve_one(engine, padded(c, f))
    assert planlib.mapsearch_call_count() == s0
    assert engine.compiled == 1
    assert sites > 0 and h.get("serve.bev_sites") == 2 * sites
    np.testing.assert_array_equal(first, again)


def _last_input(c, cfg):
    ext = cfg.sparse_shape
    for _, k, s, p, _ in cfg.stages[:-1]:
        c, _, ext = ref.strided_map(c, ext, k, s, p)
    return c, ext


def test_minkunet_served_answer_is_its_forward():
    cfg = minkunet.MinkUNetConfig(stem=8, enc=(8, 16), dec=(16, 8),
                                  classes=4, blocks=1)
    params = jax.jit(lambda k: minkunet.init_model(cfg, k))(
        jax.random.key(0))
    c, f = surface(6)
    arrays = padded(c, f)
    engine = engine_for(params, cfg)
    got = serve_one(engine, arrays)
    cj = [jnp.asarray(x) for x in arrays]
    plans = minkunet.build_plans(*cj[:3], cfg, cache=engine.cache,
                                 n_max=ROWS)
    dyn, treedef, static, _ = split_plans(plans)

    @jax.jit
    def forward(params, coords, batch, valid, feats, dyn):
        return minkunet.forward(params, SparseTensor(coords, batch, valid,
                                                     feats), cfg,
                                plans=merge_plans(treedef, static, dyn),
                                impl="ref")

    want = np.asarray(forward(params, *cj, dyn))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# The SPAC refresh's short-circuit on the served path
# ---------------------------------------------------------------------------

#: MinkUNet and SECOND narrow enough for the CPU, and at least 16 channels
#: wide after every BN + ReLU, so that a row of random-weight features is
#: all zero with odds of about 2^-16 or less
SPAC_MODELS = {
    "minkunet": minkunet.MinkUNetConfig(stem=32, enc=(32, 32),
                                        dec=(32, 32), classes=4, blocks=1),
    "second": dataclasses.replace(TINY, stem=(16, 16), stages=tuple(
        (max(c, 16), *rest) for c, *rest in TINY.stages)),
}


def _subm3_convs(cfg):
    if isinstance(cfg, minkunet.MinkUNetConfig):
        return 1 + cfg.blocks * (len(cfg.enc) + len(cfg.dec))
    return len(cfg.stem) + sum(blocks for *_, blocks in cfg.stages)


def _init(cfg):
    model = minkunet if isinstance(cfg, minkunet.MinkUNetConfig) else second
    return model, jax.jit(lambda k: model.init_model(cfg, k))(
        jax.random.key(8))


@pytest.mark.parametrize("name", list(SPAC_MODELS))
def test_subm3_kmap_reads_only_valid_rows(name):
    """Every kmap entry >= 0 of a Subm3 plan points at a row whose
    ``valid`` bit the plan was built from is set, so a liveness check
    over valid rows covers every row a valid slot reads. Padding rows
    repeat valid coordinates, so a search that indexed them would
    show."""
    cfg = SPAC_MODELS[name]
    model, _ = _init(cfg)
    c, f = surface(9)
    coords, batch, valid, _ = padded(c, f)
    coords[c.shape[0]:] = c[:ROWS - c.shape[0]]
    plans = model.build_plans(jnp.asarray(coords), jnp.asarray(batch),
                              jnp.asarray(valid), cfg)
    masks = [valid] + [np.asarray(d.out_valid) for d in plans.down]
    subms = [(p, m) for p, m in zip(plans.subm, masks) if p is not None]
    assert len(subms) == len(masks) - (name == "second")
    for plan, mask in subms:
        kmap = np.asarray(plan.kmap)
        assert (kmap >= 0).any() and (~mask).any()
        assert mask[kmap[kmap >= 0]].all()


@pytest.mark.parametrize("name", list(SPAC_MODELS))
def test_engine_counts_spac_refreshes(name, monkeypatch):
    """``spac.refresh`` counts every Subm3 conv of every served cloud and
    ``spac.refresh_full`` none of them on ordinary features. A cloud with
    one all-zero voxel row at the stem's input takes the full refresh at
    least once and is answered bit for bit as by the path without the
    short-circuit."""
    cfg = SPAC_MODELS[name]
    _, params = _init(cfg)
    convs = _subm3_convs(cfg)
    engine = engine_for(params, cfg)
    with guard.scoped_health() as h:
        for seed in (5, 6):
            serve_one(engine, padded(*surface(seed)))
        assert h.get("spac.refresh") == 2 * convs
        assert h.get("spac.refresh_full") == 0

    c, f = surface(7)
    f[3] = 0.0
    with guard.scoped_health() as h:
        engine.submit("r", *padded(c, f), deadline_s=600.0)
        (got,) = engine.step()
        assert h.get("spac.refresh") == convs
        assert h.get("spac.refresh_full") >= 1

    execute = planlib.execute
    monkeypatch.setattr(planlib, "execute",
                        lambda *a, src_valid=None, **k: execute(*a, **k))
    with guard.scoped_health() as h:
        engine = engine_for(params, cfg)
        engine.submit("r", *padded(c, f), deadline_s=600.0)
        (want,) = engine.step()
        assert h.get("spac.refresh_full") == convs
    assert got.status == want.status == "completed"
    assert got.digest == want.digest
