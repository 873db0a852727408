"""SECOND [6] — the paper's detection network (Det(k)/Det(n)), in the form
OpenPCDet's ``tools/cfgs/kitti_models/second.yaml`` runs it.

* ``VoxelBackBone8x`` over the SpOctA core: Subm3 convs at each
  resolution and strided sparse convs with spconv's kernel, stride,
  per-axis padding and output-extent rule (``plan.gconv3_plan``); every
  conv is bias-free and followed by BatchNorm (eps 1e-3) and ReLU.
* ``HeightCompression``: the last sparse tensor, densified to
  ``(C, D, H, W)``, becomes a ``C * D``-channel bird's-eye-view map,
  channel ``c * D + d``.
* ``BaseBEVBackbone``: per block ZeroPad(1) + a 3x3 conv of stride s and
  ``layers`` more 3x3 convs, each with BN and ReLU; a transposed-conv
  deblock (kernel = stride) per block; the deblocks' outputs concatenated.
* ``AnchorHeadSingle``: three 1x1 convs (with bias) for class, box and
  direction; the answer is the three maps side by side, one row per BEV
  cell (row ``y * W + x``). Anchor decoding and NMS are host
  post-processing and are not part of the model.

Inference BN is folded into a per-channel affine
(``spconv.fold_bn_inference``). Coordinates, kernel shapes, strides and
paddings are in (x, y, z) order; the published ones are (z, y, x).

Serving builds the geometry eagerly (:func:`build_plans`) and runs two
executables per cloud: :func:`backbone` over the plans, then
:func:`bev_head` (``SERVE_STAGES``), so that a device trace can split the
dense stage's time from the sparse one's. The plain float32 reference is
``models/second_reference.py``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import mapsearch
from repro.core import plan as planlib
from repro.core import spconv
from repro.core.spconv import SparseTensor

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class SECONDConfig:
    name: str = "second-kitti"
    in_ch: int = 4                      # MeanVFE: 4 features per voxel
    sparse_shape: tuple = (1408, 1600, 41)   # (x, y, z) voxels
    stem: tuple = (16, 16)              # Subm3 widths at full resolution:
                                        # conv_input, conv1
    # strided convs: (cout, kernel, stride, padding, Subm3 blocks after)
    stages: tuple = ((32, (3, 3, 3), (2, 2, 2), (1, 1, 1), 2),     # conv2
                     (64, (3, 3, 3), (2, 2, 2), (1, 1, 1), 2),     # conv3
                     (64, (3, 3, 3), (2, 2, 2), (1, 1, 0), 2),     # conv4
                     (128, (1, 1, 3), (1, 1, 2), (0, 0, 0), 0))    # conv_out
    bev_layers: tuple = (5, 5)          # extra 3x3 convs per block
    bev_strides: tuple = (1, 2)
    bev_filters: tuple = (128, 256)
    up_strides: tuple = (1, 2)
    up_filters: tuple = (256, 256)
    anchors: int = 6                    # per BEV cell: 3 classes x 2 yaws
    classes: int = 3
    box_code: int = 7
    dir_bins: int = 2
    bn_eps: float = 1e-3
    grid_bits: int = 7
    batch_bits: int = 4


def extents(cfg: SECONDConfig) -> list:
    """The (x, y, z) extent of every resolution, full first."""
    out = [tuple(cfg.sparse_shape)]
    for _, k, s, p, _ in cfg.stages:
        out.append(mapsearch.strided_out_extent(out[-1], k, s, p))
    return out


def bev_shape(cfg: SECONDConfig) -> tuple:
    """``(H, W, C)`` of the HeightCompression map."""
    w, h, d = extents(cfg)[-1]
    return h, w, cfg.stages[-1][0] * d


def head_widths(cfg: SECONDConfig) -> dict:
    a = cfg.anchors
    return {"cls": a * cfg.classes, "box": a * cfg.box_code,
            "dir": a * cfg.dir_bins}


def _sparse_layers(cfg: SECONDConfig) -> list:
    """``(path, taps, cin, cout)`` of every sparse conv, in order."""
    out, c = [], cfg.in_ch
    for j, w in enumerate(cfg.stem):
        out.append(((f"stem{j}",), 27, c, w))
        c = w
    for i, (w, k, _, _, blocks) in enumerate(cfg.stages):
        taps = k[0] * k[1] * k[2]
        out.append(((f"stage{i}", "down"), taps, c, w))
        out += [((f"stage{i}", f"block{b}"), 27, w, w) for b in range(blocks)]
        c = w
    return out


def _dense_layers(cfg: SECONDConfig) -> list:
    """``(path, shape)`` of every dense conv weight, in order: the BEV
    blocks' 3x3 convs (HWIO) and the deblocks (``(s, s, cin, cout)``)."""
    out, c = [], bev_shape(cfg)[2]
    for i, (n, f) in enumerate(zip(cfg.bev_layers, cfg.bev_filters)):
        for j in range(n + 1):
            out.append((("bev", f"block{i}", f"conv{j}"),
                        (3, 3, c if j == 0 else f, f)))
        c = f
    for i, (s, f) in enumerate(zip(cfg.up_strides, cfg.up_filters)):
        out.append((("bev", f"deblock{i}"), (s, s, cfg.bev_filters[i], f)))
    return out


def _put(tree: dict, path: tuple, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def init_model(cfg: SECONDConfig, key) -> dict:
    """He-normal weights, identity BatchNorm, zero head bias."""
    sparse, dense = _sparse_layers(cfg), _dense_layers(cfg)
    keys = iter(jax.random.split(key, len(sparse) + len(dense) + 3))
    p: dict = {}
    for path, taps, cin, cout in sparse:
        _put(p, path, {"w": spconv.init_conv(next(keys), taps, cin,
                                             cout)["w"],
                       "bn": spconv.init_batchnorm(cout)})
    for path, shape in dense:
        fan_in = shape[0] * shape[1] * shape[2]
        _put(p, path, {"w": jax.random.normal(next(keys), shape)
                       * (2.0 / fan_in) ** 0.5,
                       "bn": spconv.init_batchnorm(shape[3])})
    c = sum(cfg.up_filters)
    p["head"] = {name: {"w": jax.random.normal(next(keys), (c, w))
                        * (1.0 / c) ** 0.5, "b": jnp.zeros((w,))}
                 for name, w in head_widths(cfg).items()}
    return p


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------

class SECONDPlans(NamedTuple):
    """Every geometry-determined plan of one SECOND pass."""

    subm: tuple   # per resolution: the Subm3 plan (None where none runs)
    down: tuple   # per stage: the strided plan (carries the next coords)


def plans_per_cloud(cfg: SECONDConfig) -> int:
    """Plans one fresh cloud adds to a PlanCache."""
    return len(cfg.stages) + bool(cfg.stem) + sum(
        1 for *_, blocks in cfg.stages if blocks)


@functools.partial(jax.profiler.annotate_function, name="plan.build")
def build_plans(coords, batch, valid, cfg: SECONDConfig, *,
                cache: planlib.PlanCache | None = None,
                n_max: int | None = None,
                replan: bool | None = None) -> SECONDPlans:
    """Build (or fetch) the plan set of one coordinate set: one Subm3
    search per resolution that runs Subm3 convs and one strided search
    per stage (8 for ``second.yaml``). Every resolution keeps ``n_max``
    rows; a strided conv whose outputs exceed them replans at escalated
    budget (``guard.with_replan``) unless ``replan`` is False. The last
    stage's output sites (``ConvPlan.sites``), the active cells of the
    BEV map, are added to the ``serve.bev_sites`` health counter for
    every cloud, a cached plan's too; the returned plans carry no
    ``sites``, so every geometry of a bucket has one static skeleton."""
    from repro.runtime import guard
    if replan is None:
        replan = guard.replan_retries() > 0
    if cache is None:
        cache = planlib.PlanCache()
    n_max = coords.shape[0] if n_max is None else n_max
    gb, bb = cfg.grid_bits, cfg.batch_bits
    ext = extents(cfg)

    def subm(r, c, b, v):
        def build(mb):
            return planlib.subm3_plan(c, b, v, max_blocks=mb, grid_bits=gb,
                                      batch_bits=bb, cache=cache)
        with TraceAnnotation("plan.subm3", r=r):
            if not replan:
                return build(n_max)
            return guard.with_replan(build, n_max,
                                     key=("second-subm3", c.shape[0], gb, bb))

    cur = (coords, batch, valid)
    subms = [subm(0, *cur) if cfg.stem else None]
    downs = []
    last = len(cfg.stages) - 1
    for i, (_, k, s, p, blocks) in enumerate(cfg.stages):
        def build(budget, cur=cur, i=i, k=k, s=s, p=p):
            return planlib.gconv3_plan(
                *cur, grid_bits=gb, batch_bits=bb, out_budget=budget,
                kernel=k, stride=s, padding=p, extent=ext[i], cache=cache)
        with TraceAnnotation("plan.gconv3", r=i + 1):
            d = guard.with_replan(build, n_max, key=(
                "second-gconv3", i, cur[0].shape[0], gb, bb)) \
                if replan else build(n_max)
        if i == last and d.sites is not None:
            guard.health().note("serve.bev_sites", d.sites)
        cur = (d.out_coords, d.out_batch, d.out_valid)
        downs.append(d._replace(sites=None))
        subms.append(subm(i + 1, *cur) if blocks else None)
    return SECONDPlans(tuple(subms), tuple(downs))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _bn_relu(x, bn, eps):
    s, t = spconv.fold_bn_inference(None, bn, eps=eps)
    return jnp.maximum(x * s + t, 0.0)


def backbone(params, st: SparseTensor, cfg: SECONDConfig, *,
             plans: SECONDPlans, impl: str | None = None) -> SparseTensor:
    """``VoxelBackBone8x`` over prebuilt plans: the last resolution's
    sparse tensor (``stages[-1]`` channels). The Subm3 convs skip
    all-zero rows (SPAC, §V-B), as MinkUNet's do."""
    st = spconv.mask_feats(st)

    def conv(st, layer, plan, spac):
        y = planlib.execute(plan, st.feats, layer["w"], None, spac=spac,
                            impl=impl)
        if plan.out_coords is not None:
            st = SparseTensor(plan.out_coords, plan.out_batch,
                              plan.out_valid, y)
        y = _bn_relu(y, layer["bn"], cfg.bn_eps)
        return st.replace_feats(jnp.where(st.valid[:, None], y, 0.0))

    with jax.named_scope("fwd.subm3"):
        for j in range(len(cfg.stem)):
            st = conv(st, params[f"stem{j}"], plans.subm[0], True)
    for i, (*_, blocks) in enumerate(cfg.stages):
        stage = params[f"stage{i}"]
        with jax.named_scope("fwd.down"):
            st = conv(st, stage["down"], plans.down[i], False)
        with jax.named_scope("fwd.subm3"):
            for b in range(blocks):
                st = conv(st, stage[f"block{b}"], plans.subm[i + 1], True)
    return st


def to_bev(st: SparseTensor, cfg: SECONDConfig, *,
           n_batch: int = 1) -> jnp.ndarray:
    """``HeightCompression``: ``(n_batch, H, W, C * D)``, channel
    ``c * D + d``; cloud ``b`` is the voxels of ``st.batch == b``."""
    w, h, d = extents(cfg)[-1]
    c = st.feats.shape[1]
    cell = (st.batch * h + st.coords[:, 1]) * w + st.coords[:, 0]
    cell = jnp.where(st.valid, cell, n_batch * h * w)
    z = jnp.clip(st.coords[:, 2], 0, d - 1)
    bev = jnp.zeros((n_batch * h * w, d, c), jnp.float32)
    bev = bev.at[cell, z].set(st.feats.astype(jnp.float32), mode="drop")
    return bev.transpose(0, 2, 1).reshape(n_batch, h, w, c * d)


def _conv3x3(x, w, stride):
    """ZeroPad2d(1) + 3x3 conv of ``stride`` over ``(B, H, W, C)`` maps."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _deconv(x, w):
    """``ConvTranspose2d`` with kernel = stride = ``s``:
    ``out[s*y + a, s*x + b] = x[y, x] @ w[a, b]``."""
    s = w.shape[0]
    n, h, wd, _ = x.shape
    y = jnp.einsum("nhwi,abio->nhawbo", x, w, precision=HIGHEST)
    return y.reshape(n, h * s, wd * s, w.shape[3])


def bev_head(params, st: SparseTensor, cfg: SECONDConfig, *,
             n_batch: int = 1) -> jnp.ndarray:
    """``HeightCompression``, ``BaseBEVBackbone`` and ``AnchorHeadSingle``:
    ``(n_batch * H * W, cls + box + dir)`` float32 rows, one per BEV cell
    of each cloud (row ``(b * H + y) * W + x``)."""
    eps, p = cfg.bn_eps, params["bev"]
    with jax.named_scope("fwd.bev"):
        x, ups = to_bev(st, cfg, n_batch=n_batch), []
        for i, (n, s) in enumerate(zip(cfg.bev_layers, cfg.bev_strides)):
            blk = p[f"block{i}"]
            for j in range(n + 1):
                x = _bn_relu(_conv3x3(x, blk[f"conv{j}"]["w"],
                                      s if j == 0 else 1),
                             blk[f"conv{j}"]["bn"], eps)
            de = p[f"deblock{i}"]
            ups.append(_bn_relu(_deconv(x, de["w"]), de["bn"], eps))
        x = jnp.concatenate(ups, axis=-1)
    with jax.named_scope("fwd.head"):
        rows = x.reshape(-1, x.shape[-1])
        return jnp.concatenate(
            [jnp.dot(rows, params["head"][k]["w"], precision=HIGHEST)
             + params["head"][k]["b"] for k in head_widths(cfg)], axis=1)


#: the served forward, one executable per entry: ``(span, function)``.
#: The first takes ``(params, st, cfg, plans=, impl=)``, each later one
#: ``(params, previous output, cfg)`` and is launched inside its span.
SERVE_STAGES = ((None, "backbone"), ("serve.bev", "bev_head"))


def forward(params, st: SparseTensor, cfg: SECONDConfig, *,
            plans: SECONDPlans | None = None, impl: str | None = None,
            n_batch: int = 1) -> jnp.ndarray:
    """The head maps of ``n_batch`` clouds (:func:`bev_head`); builds
    their plans (without replanning, so it also runs under jit) when none
    are given."""
    if plans is None:
        plans = build_plans(st.coords, st.batch, st.valid, cfg, replan=False)
    return bev_head(params, backbone(params, st, cfg, plans=plans,
                                     impl=impl), cfg, n_batch=n_batch)


def detection_loss(params, batch, cfg: SECONDConfig, *, n_batch: int = 1,
                   impl: str | None = None):
    """Anchor classification (sigmoid cross-entropy) and box regression
    (smooth L1 on positive anchors) over the head maps of ``n_batch``
    clouds. ``batch``: the SparseTensor fields, ``objectness``
    ``(n_batch * H * W, anchors * classes)`` in {0, 1} and ``boxes``
    ``(n_batch * H * W, anchors * box_code)``, rows as :func:`bev_head`'s."""
    st = SparseTensor(batch["coords"], batch["batch"], batch["valid"],
                      batch["feats"])
    maps = forward(params, st, cfg, impl=impl, n_batch=n_batch)
    w = head_widths(cfg)
    cls, box = maps[:, :w["cls"]], maps[:, w["cls"]:w["cls"] + w["box"]]
    obj = batch["objectness"].astype(jnp.float32)
    cls_loss = jnp.mean(jnp.maximum(cls, 0) - cls * obj
                        + jnp.log1p(jnp.exp(-jnp.abs(cls))))
    pos = jnp.repeat(obj.reshape(obj.shape[0], cfg.anchors, -1).max(-1),
                     cfg.box_code, axis=1)
    diff = jnp.abs(box - batch["boxes"])
    huber = jnp.where(diff < 1.0, 0.5 * diff ** 2, diff - 0.5)
    box_loss = (huber * pos).sum() / jnp.maximum(pos.sum(), 1.0)
    loss = cls_loss + 2.0 * box_loss
    return loss, {"cls": cls_loss, "box": box_loss}
