"""The benchmark's own view of a MinkUNet configuration: its layer list,
its seeded weights, and the plain float32 reference forward.

Nothing here imports the program. The weights are laid out as the
program's parameter tree (``stem``, ``enc{i}``, ``dec{i}``, ``head``;
each conv ``{"w": (taps, cin, cout), "b"}`` and each BatchNorm
``{"scale", "bias", "mean", "var"}``) so that the same arrays can be
handed to the served model and to the reference.

Layer equations (inference): every sparse conv is
``out[i] = b + sum_t f[map[i, t]] @ W[t]`` over a gather-form map
(geometry.py), followed by BatchNorm ``(x - mean) / sqrt(var + 1e-5) *
scale + bias`` and ReLU. The encoder stage ``i`` is a stride-2 2x2x2
down conv and ``blocks`` 3x3x3 submanifold convs; decoder stage ``i`` a
transposed 2x2x2 conv back to the skip's resolution, a concat with the
skip's features, and ``blocks`` 3x3x3 convs; the head is a 1x1 linear.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

EPS = 1e-5


class Arch(NamedTuple):
    in_ch: int
    stem: int
    enc: tuple
    dec: tuple
    blocks: int
    classes: int


def arch(cfg: dict) -> Arch:
    return Arch(int(cfg["in_ch"]), int(cfg["stem"]), tuple(cfg["enc"]),
                tuple(cfg["dec"]), int(cfg["blocks"]), int(cfg["classes"]))


class Conv(NamedTuple):
    path: tuple      # keys into the parameter tree
    kind: str        # subm | down | up
    level_in: int    # resolution the input lives at
    level_out: int
    taps: int
    cin: int
    cout: int


def layers(a: Arch) -> list[Conv]:
    """Every sparse conv of one forward, in execution order."""
    out = [Conv(("stem",), "subm", 0, 0, 27, a.in_ch, a.stem)]
    c_prev, skips = a.stem, [a.stem]
    for i, c in enumerate(a.enc):
        out.append(Conv((f"enc{i}", "down"), "down", i, i + 1, 8, c_prev, c))
        for b in range(a.blocks):
            out.append(Conv((f"enc{i}", f"block{b}"), "subm", i + 1, i + 1,
                            27, c, c))
        c_prev = c
        skips.append(c)
    n = len(a.enc)
    for i, c in enumerate(a.dec):
        lv = n - 1 - i
        out.append(Conv((f"dec{i}", "up"), "up", lv + 1, lv, 8, c_prev, c))
        for b in range(a.blocks):
            cin = c + skips[lv] if b == 0 else c
            out.append(Conv((f"dec{i}", f"block{b}"), "subm", lv, lv, 27,
                            cin, c))
        c_prev = c
    return out


def head_width(a: Arch) -> int:
    return a.dec[-1] if a.dec else a.enc[-1]


def key_for(seed: int):
    """A JAX key from any whole number, 64-bit seeds included."""
    s = int(seed)
    k = jax.random.key(s & 0xFFFFFFFF)
    return jax.random.fold_in(k, (s >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnums=0)
def _init(a: Arch, key):
    convs = layers(a)
    keys = jax.random.split(key, 5 * len(convs) + 2)
    tree: dict = {}
    for j, cv in enumerate(convs):
        k = keys[5 * j:5 * j + 5]
        std = (2.0 / (cv.taps * cv.cin)) ** 0.5
        node = tree
        for p in cv.path[:-1]:
            node = node.setdefault(p, {})
        node[cv.path[-1]] = {
            "conv": {"w": std * jax.random.normal(
                         k[0], (cv.taps, cv.cin, cv.cout), jnp.float32),
                     "b": 0.1 * jax.random.normal(k[1], (cv.cout,))},
            "bn": {"scale": jax.random.uniform(k[2], (cv.cout,),
                                               minval=0.8, maxval=1.2),
                   "bias": 0.1 * jax.random.normal(k[3], (cv.cout,)),
                   "mean": 0.1 * jax.random.normal(
                       jax.random.fold_in(k[4], 0), (cv.cout,)),
                   "var": jax.random.uniform(jax.random.fold_in(k[4], 1),
                                             (cv.cout,), minval=0.8,
                                             maxval=1.2)}}
    c = head_width(a)
    tree["head"] = {"w": (1.0 / c) ** 0.5 * jax.random.normal(
                        keys[-2], (1, c, a.classes), jnp.float32),
                    "b": 0.1 * jax.random.normal(keys[-1], (a.classes,))}
    return tree


def init_params(a: Arch, seed: int) -> dict:
    """All weights of ``a`` from ``seed``, made on the device in one call."""
    return _init(a, key_for(seed))


def get(tree: dict, path: tuple):
    for p in path:
        tree = tree[p]
    return tree


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def dot_highest(x, w):
    """float32 contraction at full precision (the configuration's)."""
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _split(x):
    """``x`` as bf16 ``hi + lo``. ``hi`` is rounded by ``reduce_precision``,
    which XLA keeps: a float32 -> bf16 -> float32 round trip may be folded
    away (excess precision is allowed on the TPU), which leaves ``lo`` 0."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def dot_bf16x3(x, w):
    """The next precision down: three bf16 passes (what ``HIGH`` does on
    the TPU), written out so that it means the same on every backend."""
    xh, xl = _split(x)
    wh, wl = _split(w)
    d = partial(jnp.dot, preferred_element_type=jnp.float32)
    return d(xh, wh) + (d(xh, wl) + d(xl, wh))


DOTS = {"highest": dot_highest, "bf16x3": dot_bf16x3}


def _conv(f, kmap, w, b, dot):
    """``b + sum_t f[kmap[:, t]] @ w[t]``, tap by tap (a scan, so that a
    forward compiles as one loop per conv); -1 reads zeros."""
    fz = jnp.concatenate([f, jnp.zeros((1, f.shape[1]), f.dtype)])
    idx = jnp.where(kmap < 0, f.shape[0], kmap).T

    def tap(out, xs):
        i, wt = xs
        return out + dot(jnp.take(fz, i, axis=0), wt), None

    out = jnp.zeros((kmap.shape[0], w.shape[2]), jnp.float32) + b
    return jax.lax.scan(tap, out, (idx, w))[0]


def _bn_relu(x, bn):
    y = (x - bn["mean"]) / jnp.sqrt(bn["var"] + EPS) * bn["scale"] \
        + bn["bias"]
    return jnp.maximum(y, 0.0)


@partial(jax.jit, static_argnums=(0, 1))
def _forward(a: Arch, precision: str, params, feats, subm, down, up):
    dot = DOTS[precision]
    convs = iter(layers(a))

    def apply(x, kmap):
        cv = next(convs)
        p = get(params, cv.path)
        return _bn_relu(_conv(x, kmap, p["conv"]["w"], p["conv"]["b"], dot),
                        p["bn"])

    x = apply(feats, subm[0])
    skips = [x]
    for i in range(len(a.enc)):
        x = apply(x, down[i])
        for _ in range(a.blocks):
            x = apply(x, subm[i + 1])
        skips.append(x)
    n = len(a.enc)
    for i in range(len(a.dec)):
        lv = n - 1 - i
        x = apply(x, up[lv])
        x = jnp.concatenate([x, skips[lv]], axis=1)
        for _ in range(a.blocks):
            x = apply(x, subm[lv])
    return dot(x, params["head"]["w"][0]) + params["head"]["b"]


def padded_maps(hier, rows: int):
    """The hierarchy's maps padded to ``rows`` rows each (padding rows
    read nothing), so every cloud of one bucket shares one program."""
    def pad(m):
        out = np.full((rows, m.shape[1]), -1, np.int32)
        out[:m.shape[0]] = m
        return out
    return ([pad(lv.subm) for lv in hier.levels], [pad(d) for d in hier.down],
            [pad(u) for u in hier.up])


def forward(a: Arch, params, feats: np.ndarray, hier, rows: int, *,
            precision: str = "highest") -> np.ndarray:
    """Reference logits ``(N, classes)`` of one cloud's ``N`` voxels, in
    the order of ``feats``; ``rows`` is the padded row count."""
    n = feats.shape[0]
    f = np.zeros((rows, feats.shape[1]), np.float32)
    f[:n] = feats
    subm, down, up = padded_maps(hier, rows)
    out = _forward(a, precision, params, f, subm, down, up)
    return np.asarray(out)[:n]
