"""MinkUNet / SECOND on synthetic clouds: shapes, finiteness, learning."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.data import pointcloud
from repro.models import minkunet, second
from repro.optim import adamw


def _batch(kind, n, nb=1, classes=8, seed=0):
    rng = np.random.default_rng(seed)
    vb = pointcloud.make_batch(rng, kind, batch_size=nb, max_voxels=n,
                               voxel_size=0.15)
    b = {k: jnp.asarray(v) for k, v in vb._asdict().items()}
    b["labels"] = jnp.clip(b["labels"], 0, classes - 1)
    return b


def test_generators_produce_valid_voxels():
    rng = np.random.default_rng(0)
    for kind in ("indoor", "lidar"):
        vb = pointcloud.make_batch(rng, kind, batch_size=2, max_voxels=512)
        assert vb.valid.sum() > 100
        assert vb.coords[vb.valid].min() >= 0
        # no duplicate (batch, coord) among valid voxels
        keys = {(int(b),) + tuple(c) for c, b, v in
                zip(vb.coords, vb.batch, vb.valid) if v}
        assert len(keys) == int(vb.valid.sum())


def test_minkunet_learns_on_synthetic_segmentation():
    cfg = minkunet.MinkUNetConfig(stem=8, enc=(8, 16, 16, 16),
                                  dec=(16, 8, 8, 8), classes=8)
    params = minkunet.init_model(cfg, jax.random.key(0))
    opt_cfg = adamw.AdamWConfig(lr=2e-3, total_steps=8, warmup_steps=1)
    opt = adamw.init(params)
    batch = _batch("indoor", 512)

    @jax.jit
    def step(p, o):
        (loss, m), g = jax.value_and_grad(
            lambda pp: minkunet.segmentation_loss(pp, batch, cfg),
            has_aux=True)(p)
        p, o, _ = adamw.update(opt_cfg, g, o, p)
        return p, o, loss

    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_second_detection_pipeline():
    cfg = second.SECONDConfig(
        name="second-tiny", sparse_shape=(32, 32, 41), stem=(8,),
        stages=((8, (3, 3, 3), (2, 2, 2), (1, 1, 1), 1),
                (8, (3, 3, 3), (2, 2, 2), (1, 1, 1), 1),
                (16, (3, 3, 3), (2, 2, 2), (1, 1, 0), 1),
                (16, (1, 1, 3), (1, 1, 2), (0, 0, 0), 0)),
        bev_layers=(1, 1), bev_filters=(16, 16), up_filters=(16, 16))
    params = second.init_model(cfg, jax.random.key(1))
    # two clouds on one grid, so their BEV cells coincide
    rng = np.random.default_rng(0)
    x, y = np.meshgrid(np.arange(32), np.arange(32), indexing="ij")
    coords, ids = [], []
    for b in range(2):
        keep = rng.random(x.shape) < 0.35
        coords.append(np.stack([x[keep], y[keep],
                                10 + (x[keep] + y[keep]) // 12], 1))
        ids.append(np.full(keep.sum(), b))
    coords, ids = np.concatenate(coords), np.concatenate(ids)
    n, rows = coords.shape[0], 768
    batch = {"coords": jnp.zeros((rows, 3), jnp.int32).at[:n].set(coords),
             "batch": jnp.zeros((rows,), jnp.int32).at[:n].set(ids),
             "valid": jnp.arange(rows) < n,
             "feats": jnp.zeros((rows, 4)).at[:n].set(
                 rng.normal(size=(n, 4)))}
    h, w, _ = second.bev_shape(cfg)
    widths = second.head_widths(cfg)
    batch["objectness"] = jnp.zeros((2 * h * w, widths["cls"])).at[
        5:7].set(1.0)
    batch["boxes"] = jnp.zeros((2 * h * w, widths["box"]))
    st = second.SparseTensor(batch["coords"], batch["batch"], batch["valid"],
                             batch["feats"])

    @jax.jit
    def step(p):
        (loss, _), grads = jax.value_and_grad(
            lambda q: second.detection_loss(q, batch, cfg, n_batch=2),
            has_aux=True)(p)
        # BEV densification preserves mass: no cloud's voxel lands on
        # another's
        mid = second.backbone(p, st, cfg, plans=second.build_plans(
            st.coords, st.batch, st.valid, cfg, replan=False))
        mass = jnp.where(mid.valid[:, None], mid.feats, 0).sum()
        return loss, grads, mass, second.to_bev(mid, cfg, n_batch=2).sum()

    loss, grads, mass, bev_mass = step(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))
    assert float(mass) > 0
    np.testing.assert_allclose(float(mass), float(bev_mass), rtol=1e-5)
