"""The benchmark's own view of a voxel-to-BEV detector (family
``voxel_bev``: OpenPCDet's ``VoxelBackBone8x`` -> ``HeightCompression``
-> ``BaseBEVBackbone`` -> ``AnchorHeadSingle``, as SECOND runs them):
its layer list, its seeded weights and the plain float32 reference
forward.

Nothing here imports the program. The weights are laid out as the
program's parameter tree (``stem{j}``; ``stage{i}`` with ``down`` and
``block{b}``; ``bev`` with ``block{i}/conv{j}`` and ``deblock{i}``;
``head`` with ``cls``, ``box``, ``dir``), every conv ``{"w", "bn"}``
without bias, so that the same arrays can be handed to the served model
and to the reference.

Layer equations (inference; axes x, y, z, taps x-fastest):

* sparse conv: ``out[o] = sum_k f[in(o, k)] @ W[k]`` over a gather-form
  map. Submanifold 3x3x3: ``in(o, k) = o + k - 1``. Strided (kernel K,
  stride s, padding p per axis): ``in(o, k) = o * s - p + k``, and ``o``
  exists where some input feeds it and ``0 <= o < (D + 2p - K) // s +
  1`` for the input extent D (spconv's rule). Then BatchNorm ``(x -
  mean) / sqrt(var + eps) * scale + bias`` and ReLU.
* HeightCompression: the last tensor densified to ``(C, D, H, W)`` and
  read as ``C * D`` channels, channel ``c * D + d``.
* BEV block: ZeroPad(1) + a 3x3 conv of stride s, then ``n`` 3x3 convs
  of stride 1, each with BN and ReLU; deblock: a transposed conv with
  kernel = stride s (``out[s*y + a, s*x + b] = x[y, x] @ W[a, b]``), BN,
  ReLU; the deblocks' outputs concatenated.
* head: three 1x1 convs with bias, side by side, one row per BEV cell
  (row ``y * W + x``).
"""
from __future__ import annotations

import itertools
from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

import geometry
import reference as common


class Arch(NamedTuple):
    in_ch: int
    sparse_shape: tuple      # (x, y, z) voxels
    stem: tuple              # Subm3 widths at full resolution
    stages: tuple            # (cout, kernel, stride, padding, subm blocks)
    bev_layers: tuple
    bev_strides: tuple
    bev_filters: tuple
    up_strides: tuple
    up_filters: tuple
    anchors: int
    classes: int
    box_code: int
    dir_bins: int
    bn_eps: float


def _xyz(zyx) -> tuple:
    return tuple(int(v) for v in reversed(zyx))


def arch(cfg: dict) -> Arch:
    """The configuration file's layers (published axis order z, y, x)."""
    bev, head = cfg["bev"], cfg["head"]
    stages = tuple((int(s["out"]), _xyz(s["kernel_zyx"]),
                    _xyz(s["stride_zyx"]), _xyz(s["padding_zyx"]),
                    int(s["subm"])) for s in cfg["stages"])
    return Arch(int(cfg["in_ch"]), _xyz(cfg["sparse_shape_zyx"]),
                tuple(cfg["stem"]), stages, tuple(bev["layer_nums"]),
                tuple(bev["layer_strides"]), tuple(bev["num_filters"]),
                tuple(bev["upsample_strides"]),
                tuple(bev["num_upsample_filters"]),
                int(head["anchors_per_location"]), int(head["classes"]),
                int(head["box_code_size"]), int(head["dir_bins"]),
                float(cfg["bn_eps"]))


def out_extent(extent, kernel, stride, padding) -> tuple:
    return tuple((d + 2 * p - k) // s + 1
                 for d, k, s, p in zip(extent, kernel, stride, padding))


def extents(a: Arch) -> list:
    out = [a.sparse_shape]
    for _, k, s, p, _ in a.stages:
        out.append(out_extent(out[-1], k, s, p))
    return out


def bev_hwc(a: Arch) -> tuple:
    w, h, d = extents(a)[-1]
    return h, w, a.stages[-1][0] * d


def head_widths(a: Arch) -> dict:
    return {"cls": a.anchors * a.classes, "box": a.anchors * a.box_code,
            "dir": a.anchors * a.dir_bins}


def sparse_layers(a: Arch) -> list:
    """``(path, kind, level_in, taps, cin, cout)`` of every sparse conv."""
    out, c = [], a.in_ch
    for j, w in enumerate(a.stem):
        out.append(((f"stem{j}",), "subm", 0, 27, c, w))
        c = w
    for i, (w, k, _, _, blocks) in enumerate(a.stages):
        out.append(((f"stage{i}", "down"), "down", i, k[0] * k[1] * k[2],
                    c, w))
        out += [((f"stage{i}", f"block{b}"), "subm", i + 1, 27, w, w)
                for b in range(blocks)]
        c = w
    return out


def dense_layers(a: Arch) -> list:
    """``(path, kind, stride, (kh, kw, cin, cout), (H_in, W_in))`` of every
    dense conv (``conv`` 3x3 or ``deconv``), in order."""
    h, w, c = bev_hwc(a)
    out, size = [], (h, w)
    for i, (n, s, f) in enumerate(zip(a.bev_layers, a.bev_strides,
                                      a.bev_filters)):
        for j in range(n + 1):
            out.append((("bev", f"block{i}", f"conv{j}"), "conv",
                        s if j == 0 else 1, (3, 3, c if j == 0 else f, f),
                        size))
            if j == 0:
                size = ((size[0] - 1) // s + 1, (size[1] - 1) // s + 1)
        c = f
        up = a.up_strides[i]
        out.append((("bev", f"deblock{i}"), "deconv", up,
                    (up, up, f, a.up_filters[i]), size))
    return out


def _put(tree: dict, path: tuple, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _bn(keys, n):
    return {"scale": jax.random.uniform(keys[0], (n,), minval=0.8,
                                        maxval=1.2),
            "bias": 0.1 * jax.random.normal(keys[1], (n,)),
            "mean": 0.1 * jax.random.normal(keys[2], (n,)),
            "var": jax.random.uniform(keys[3], (n,), minval=0.8, maxval=1.2)}


@partial(jax.jit, static_argnums=0)
def _init(a: Arch, key):
    sparse, dense = sparse_layers(a), dense_layers(a)
    heads = head_widths(a)
    keys = jax.random.split(key, 5 * (len(sparse) + len(dense))
                            + 2 * len(heads))
    tree: dict = {}
    j = 0
    for path, _, _, taps, cin, cout in sparse:
        k = keys[5 * j:5 * j + 5]
        _put(tree, path, {"w": (2.0 / (taps * cin)) ** 0.5
                          * jax.random.normal(k[0], (taps, cin, cout)),
                          "bn": _bn(k[1:], cout)})
        j += 1
    for path, kind, _, shape, _ in dense:
        k = keys[5 * j:5 * j + 5]
        fan_in = shape[2] * (9 if kind == "conv" else 1)
        _put(tree, path, {"w": (2.0 / fan_in) ** 0.5
                          * jax.random.normal(k[0], shape),
                          "bn": _bn(k[1:], shape[3])})
        j += 1
    c = sum(a.up_filters)
    k = keys[5 * j:]
    tree["head"] = {name: {"w": (1.0 / c) ** 0.5 * jax.random.normal(
                               k[2 * h], (c, w)),
                           "b": 0.1 * jax.random.normal(k[2 * h + 1], (w,))}
                    for h, (name, w) in enumerate(heads.items())}
    return tree


def init_params(a: Arch, seed: int) -> dict:
    """All weights of ``a`` from ``seed``, made on the device in one call."""
    return _init(a, common.key_for(seed))


# ---------------------------------------------------------------------------
# Neighbour search (numpy)
# ---------------------------------------------------------------------------

def _kernel_offsets(kernel) -> np.ndarray:
    kx, ky, kz = kernel
    return np.array([(x, y, z) for z in range(kz) for y in range(ky)
                     for x in range(kx)], np.int64)


def strided(coords: np.ndarray, extent, kernel, stride, padding):
    """``(out coords (M, 3), map (M, taps))`` of one strided conv; outputs
    in sorted order."""
    c = coords.astype(np.int64)
    k = _kernel_offsets(kernel)
    s, p = np.asarray(stride), np.asarray(padding)
    hi = np.asarray(out_extent(extent, kernel, stride, padding))
    num = (c[:, None, :] + p - k[None]).reshape(-1, 3)
    ok = np.all((num % s == 0) & (num >= 0) & (num // s < hi), axis=1)
    out = np.unique(num[ok] // s, axis=0)
    q = (out[:, None, :] * s - p + k[None]).reshape(-1, 3)
    return out, geometry.lookup(c, q).reshape(out.shape[0], k.shape[0])


class Levels(NamedTuple):
    coords: list    # (N_r, 3) per resolution
    subm: list      # (N_r, 27) per resolution
    down: list      # (N_{r+1}, taps) per stage


def levels(a: Arch, coords: np.ndarray) -> Levels:
    cs, subm, down = [coords.astype(np.int64)], [], []
    for (_, k, s, p, _), ext in zip(a.stages, extents(a)):
        subm.append(geometry.subm_map(cs[-1]))
        out, m = strided(cs[-1], ext, k, s, p)
        cs.append(out)
        down.append(m)
    subm.append(geometry.subm_map(cs[-1]))
    return Levels(cs, subm, down)


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def _bn_relu(x, bn, eps):
    y = (x - bn["mean"]) / jnp.sqrt(bn["var"] + eps) * bn["scale"] \
        + bn["bias"]
    return jnp.maximum(y, 0.0)


def _conv3x3(x, w, stride, dot):
    h, wd, c = x.shape
    ho, wo = (h - 1) // stride + 1, (wd - 1) // stride + 1
    xp = jnp.pad(x, ((1, 1), (1, 1), (0, 0)))
    out = 0.0
    for a, b in itertools.product(range(3), range(3)):
        tap = xp[a:a + stride * (ho - 1) + 1:stride,
                 b:b + stride * (wo - 1) + 1:stride]
        out = out + dot(tap.reshape(-1, c), w[a, b])
    return out.reshape(ho, wo, -1)


def _deconv(x, w, dot):
    s = w.shape[0]
    h, wd, c = x.shape
    taps = [[dot(x.reshape(-1, c), w[a, b]).reshape(h, wd, -1)
             for b in range(s)] for a in range(s)]
    out = jnp.stack([jnp.stack(row, axis=2) for row in taps], axis=1)
    return out.reshape(h * s, wd * s, w.shape[3])


@partial(jax.jit, static_argnums=(0, 1))
def _forward(a: Arch, precision: str, params, feats, subm, down, cells):
    dot = common.DOTS[precision]
    eps = a.bn_eps
    zero = {}

    def apply(x, kmap, layer):
        b = zero.setdefault(layer["w"].shape[2],
                            jnp.zeros((layer["w"].shape[2],)))
        return _bn_relu(common._conv(x, kmap, layer["w"], b, dot),
                        layer["bn"], eps)

    x = feats
    for j in range(len(a.stem)):
        x = apply(x, subm[0], params[f"stem{j}"])
    for i, (*_, blocks) in enumerate(a.stages):
        stage = params[f"stage{i}"]
        x = apply(x, down[i], stage["down"])
        for b in range(blocks):
            x = apply(x, subm[i + 1], stage[f"block{b}"])
    h, w, c = bev_hwc(a)
    d = extents(a)[-1][2]
    # cells: the (c, d, y, x) flat index of each last-level row's channel
    # 0 entry, past the end for padding rows
    size = c * h * w
    ch = jnp.arange(x.shape[1]) * d * h * w
    dense = jnp.zeros((size,), jnp.float32).at[
        cells[:, None] + ch[None]].set(x, mode="drop")
    x = dense.reshape(c, h, w).transpose(1, 2, 0)
    ups = []
    for i, (n, s) in enumerate(zip(a.bev_layers, a.bev_strides)):
        blk = params["bev"][f"block{i}"]
        for j in range(n + 1):
            x = _bn_relu(_conv3x3(x, blk[f"conv{j}"]["w"],
                                  s if j == 0 else 1, dot),
                         blk[f"conv{j}"]["bn"], eps)
        de = params["bev"][f"deblock{i}"]
        ups.append(_bn_relu(_deconv(x, de["w"], dot), de["bn"], eps))
    rows = jnp.concatenate(ups, axis=-1).reshape(h * w, -1)
    return jnp.concatenate([dot(rows, params["head"][k]["w"])
                            + params["head"][k]["b"]
                            for k in head_widths(a)], axis=1)


def forward(a: Arch, params, feats: np.ndarray, lv: Levels, rows: int, *,
            precision: str = "highest") -> np.ndarray:
    """The head maps ``(H * W, cls + box + dir)`` of one cloud; ``rows``
    pads every resolution, so that every cloud of a bucket shares one
    program."""
    def pad(m):
        out = np.full((rows, m.shape[1]), -1, np.int32)
        out[:m.shape[0]] = m
        return out

    f = np.zeros((rows, feats.shape[1]), np.float32)
    f[:feats.shape[0]] = feats
    h, w, c = bev_hwc(a)
    last = lv.coords[-1]
    cells = np.full((rows,), c * h * w, np.int64)
    cells[:last.shape[0]] = (last[:, 2] * h + last[:, 1]) * w + last[:, 0]
    out = _forward(a, precision, params, f, [pad(m) for m in lv.subm],
                   [pad(m) for m in lv.down], cells.astype(np.int32))
    return np.asarray(out)
