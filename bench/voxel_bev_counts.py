"""Work of one voxel-to-BEV forward, counted from the geometry alone.

Sparse convs as ``counts.py`` counts them: the maps (input-output pairs
over all taps), FLOPs ``2 * maps * cin * cout`` and the compulsory bytes
at float32 (valid inputs read once, weights once, valid outputs written
once). The dense stage from its shapes: a 3x3 conv ``2 * H_out * W_out *
9 * cin * cout``, a transposed conv with kernel = stride s ``2 * (s H) *
(s W) * cin * cout`` (each output cell takes one tap), the head ``2 * H *
W * cin * (cls + box + dir)``; bytes: each layer's input map, weights and
output map at float32.
"""
from __future__ import annotations

import numpy as np

import voxel_bev_reference as model

F32 = 4


def sparse_work(a: model.Arch, lv: model.Levels) -> list[dict]:
    out = []
    for _, kind, r, taps, cin, cout in model.sparse_layers(a):
        if kind == "subm":
            maps = int((lv.subm[r] >= 0).sum())
            n_in = n_out = lv.coords[r].shape[0]
        else:
            maps = int((lv.down[r] >= 0).sum())
            n_in, n_out = lv.coords[r].shape[0], lv.coords[r + 1].shape[0]
        out.append({"kind": kind, "maps": maps, "flops": 2 * maps * cin * cout,
                    "bytes": F32 * (n_in * cin + taps * cin * cout
                                    + n_out * cout)})
    return out


def dense_work(a: model.Arch) -> list[dict]:
    """Per dense layer of one forward (the same for every cloud)."""
    out = []
    for path, kind, s, (kh, kw, cin, cout), (h, w) in model.dense_layers(a):
        if kind == "conv":
            ho, wo = (h - 1) // s + 1, (w - 1) // s + 1
            flops = 2 * ho * wo * kh * kw * cin * cout
        else:
            ho, wo = h * s, w * s
            flops = 2 * ho * wo * cin * cout
        out.append({"layer": "/".join(path), "flops": flops,
                    "bytes": F32 * (h * w * cin + kh * kw * cin * cout
                                    + ho * wo * cout)})
    h, w, _ = model.bev_hwc(a)
    cin, cout = sum(a.up_filters), sum(model.head_widths(a).values())
    out.append({"layer": "head", "flops": 2 * h * w * cin * cout,
                "bytes": F32 * (h * w * cin + cin * cout + cout
                                + h * w * cout)})
    return out


def cloud_work(a: model.Arch, coords: np.ndarray, peaks: dict) -> dict:
    """One cloud's totals: ``conv_flops`` and ``conv_min_s`` of the sparse
    convs (as counts.py), ``model_flops`` (sparse and dense), ``bev_min_s``
    (the dense stage's least time: its FLOPs over the bf16 peak or its
    bytes over the HBM peak, the larger) and ``bev_cells`` (H * W)."""
    sparse = sparse_work(a, model.levels(a, coords))
    dense = dense_work(a)
    flops = sum(c["flops"] for c in sparse)
    dense_flops = sum(d["flops"] for d in dense)
    h, w, _ = model.bev_hwc(a)
    return {"conv_flops": flops, "model_flops": flops + dense_flops,
            "conv_min_s": sum(max(c["flops"] / peaks["flops_per_s"],
                                  c["bytes"] / peaks["bytes_per_s"])
                              for c in sparse),
            "bev_min_s": max(dense_flops / peaks["flops_per_s"],
                             sum(d["bytes"] for d in dense)
                             / peaks["bytes_per_s"]),
            "bev_cells": h * w}
