"""Plain float32 reference of SECOND (``models/second.py``).

The same layers in straightforward ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, with its own neighbour
search for every sparse conv (a host dictionary of the voxels, probed
per output and kernel offset) and spconv's output rule written out: a
strided conv's output ``o`` takes input ``o * s - p + k`` through offset
``k`` and exists where some input feeds it and ``0 <= o < (D + 2p - K) //
s + 1``. No kernels, plans or caches. Small clouds only: the search is
a Python loop. The layers run as one jitted program.
"""
from __future__ import annotations

import functools
import itertools

import numpy as np
import jax
import jax.numpy as jnp

from repro.models.second import SECONDConfig


def _index(coords) -> dict:
    return {tuple(c): i for i, c in enumerate(np.asarray(coords).tolist())}


def _offsets(kernel) -> list:
    """Kernel offsets ``(kx, ky, kz)``, x fastest (the weights' tap order)."""
    kx, ky, kz = kernel
    return [(x, y, z) for z in range(kz) for y in range(ky) for x in range(kx)]


def subm_map(coords) -> np.ndarray:
    """(N, 27): the input at ``coords[i] + d`` per offset, or -1."""
    where = _index(coords)
    return np.array([[where.get((x + dx - 1, y + dy - 1, z + dz - 1), -1)
                      for dx, dy, dz in _offsets((3, 3, 3))]
                     for x, y, z in np.asarray(coords).tolist()],
                    np.int32).reshape(-1, 27)


def strided_map(coords, extent, kernel, stride, padding):
    """``(out coords, (N_out, taps) map)`` of one strided conv."""
    where = _index(coords)
    hi = [(d + 2 * p - k) // s + 1
          for d, k, s, p in zip(extent, kernel, stride, padding)]
    outs = set()
    for c in where:
        for k in _offsets(kernel):
            num = [ci + p - ki for ci, p, ki in zip(c, padding, k)]
            if all(n % s == 0 and 0 <= n // s < h
                   for n, s, h in zip(num, stride, hi)):
                outs.add(tuple(n // s for n, s in zip(num, stride)))
    out = sorted(outs)
    kmap = np.array([[where.get(tuple(oi * s - p + ki for oi, s, p, ki in
                                      zip(o, stride, padding, k)), -1)
                      for k in _offsets(kernel)] for o in out], np.int32)
    return (np.array(out, np.int32).reshape(-1, 3),
            kmap.reshape(len(out), -1), tuple(hi))


def _conv(x, kmap, w):
    """``out[o] = sum_t x[kmap[o, t]] @ w[t]``; -1 reads zeros."""
    xz = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    idx = jnp.where(kmap < 0, x.shape[0], kmap)
    return jnp.einsum("otc,tcd->od", xz[idx], w)


def _bn_relu(x, bn, eps):
    y = (x - bn["mean"]) / jnp.sqrt(bn["var"] + eps) * bn["scale"] \
        + bn["bias"]
    return jnp.maximum(y, 0.0)


def _conv3x3(x, w, stride):
    """ZeroPad2d(1) then a 3x3 conv of ``stride``, over the nine taps."""
    h, wd, _ = x.shape
    ho, wo = (h - 1) // stride + 1, (wd - 1) // stride + 1
    xp = jnp.pad(x, ((1, 1), (1, 1), (0, 0)))
    taps = jnp.stack([xp[a:a + stride * (ho - 1) + 1:stride,
                         b:b + stride * (wo - 1) + 1:stride]
                      for a, b in itertools.product(range(3), range(3))],
                     axis=2)                          # (ho, wo, 9, cin)
    return jnp.einsum("hwtc,tcd->hwd", taps, w.reshape(9, *w.shape[2:]))


def _deconv(x, w):
    """Transposed conv, kernel = stride = s: ``out[s*y + a, s*x + b] =
    x[y, x] @ w[a, b]``."""
    s = w.shape[0]
    h, wd, _ = x.shape
    y = jnp.einsum("hwc,abcd->hawbd", x, w)
    return y.reshape(h * s, wd * s, w.shape[3])


def forward(params, coords, feats, cfg: SECONDConfig) -> np.ndarray:
    """The head maps ``(H * W, cls + box + dir)`` of one cloud of ``N``
    voxels ``coords`` (N, 3) x, y, z with ``feats`` (N, in_ch)."""
    c, ext = np.asarray(coords), tuple(cfg.sparse_shape)
    maps = [subm_map(c)] * len(cfg.stem)
    for _, k, s, p, blocks in cfg.stages:
        c, m, ext = strided_map(c, ext, k, s, p)
        maps += [m] + [subm_map(c)] * blocks
    with jax.default_matmul_precision("highest"):
        out = _forward(params, jnp.asarray(feats, jnp.float32),
                       [jnp.asarray(m) for m in maps], jnp.asarray(c), cfg)
    return np.asarray(out)


@functools.partial(jax.jit, static_argnums=4)
def _forward(params, x, maps, c, cfg):
    eps = cfg.bn_eps
    layers = [params[f"stem{j}"] for j in range(len(cfg.stem))]
    for i, (*_, blocks) in enumerate(cfg.stages):
        stage = params[f"stage{i}"]
        layers += [stage["down"]] + [stage[f"block{b}"] for b in range(blocks)]
    for layer, m in zip(layers, maps):
        x = _bn_relu(_conv(x, m, layer["w"]), layer["bn"], eps)
    # HeightCompression: dense (C, D, H, W) viewed as (C * D, H, W)
    w, h, d = _extent(cfg)
    dense = jnp.zeros((x.shape[1], d, h, w), jnp.float32)
    dense = dense.at[:, c[:, 2], c[:, 1], c[:, 0]].set(x.T)
    x = dense.reshape(-1, h, w).transpose(1, 2, 0)
    bev, ups = params["bev"], []
    for i, (n, s) in enumerate(zip(cfg.bev_layers, cfg.bev_strides)):
        blk = bev[f"block{i}"]
        for j in range(n + 1):
            x = _bn_relu(_conv3x3(x, blk[f"conv{j}"]["w"], s if j == 0 else 1),
                         blk[f"conv{j}"]["bn"], eps)
        de = bev[f"deblock{i}"]
        ups.append(_bn_relu(_deconv(x, de["w"]), de["bn"], eps))
    rows = jnp.concatenate(ups, axis=-1).reshape(h * w, -1)
    head = params["head"]
    return jnp.concatenate([rows @ head[k]["w"] + head[k]["b"]
                            for k in ("cls", "box", "dir")], axis=1)


def _extent(cfg: SECONDConfig) -> tuple:
    ext = tuple(cfg.sparse_shape)
    for _, k, s, p, _ in cfg.stages:
        ext = tuple((d + 2 * pi - ki) // si + 1
                    for d, ki, si, pi in zip(ext, k, s, p))
    return ext
