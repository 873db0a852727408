"""Work of one forward, counted from the geometry alone.

For every sparse conv: the maps (input-output pairs over all taps), the
FLOPs ``2 * maps * cin * cout`` and the compulsory bytes at float32 —
the valid inputs read once, the weights once, the valid outputs written
once. Padding, tiles and skipped work are the implementation's business
and are not counted, so the counts are the same whatever runs the layer.
"""
from __future__ import annotations

import numpy as np

import geometry
import reference as model

F32 = 4


def conv_work(a: model.Arch, hier) -> list[dict]:
    """Per conv of one forward: ``{"maps", "flops", "bytes", "taps",
    "cin", "cout"}``."""
    out = []
    for cv in model.layers(a):
        if cv.kind == "subm":
            maps = int((hier.levels[cv.level_out].subm >= 0).sum())
        elif cv.kind == "down":
            maps = int((hier.down[cv.level_in] >= 0).sum())
        else:
            maps = int((hier.up[cv.level_out] >= 0).sum())
        n_in = hier.levels[cv.level_in].coords.shape[0]
        n_out = hier.levels[cv.level_out].coords.shape[0]
        out.append({"kind": cv.kind, "maps": maps, "taps": cv.taps,
                    "cin": cv.cin, "cout": cv.cout,
                    "flops": 2 * maps * cv.cin * cv.cout,
                    "bytes": F32 * (n_in * cv.cin + cv.taps * cv.cin * cv.cout
                                    + n_out * cv.cout)})
    return out


def cloud_work(a: model.Arch, coords: np.ndarray, peaks: dict) -> dict:
    """One cloud's totals: ``conv_flops``, ``model_flops`` (convs and
    head) and ``conv_min_s``, the sum over convs of
    ``max(flops / peak FLOP/s, bytes / peak B/s)``."""
    hier = geometry.hierarchy(coords, len(a.enc))
    convs = conv_work(a, hier)
    n = coords.shape[0]
    head = 2 * n * model.head_width(a) * a.classes
    min_s = sum(max(c["flops"] / peaks["flops_per_s"],
                    c["bytes"] / peaks["bytes_per_s"]) for c in convs)
    return {"conv_flops": sum(c["flops"] for c in convs),
            "model_flops": sum(c["flops"] for c in convs) + head,
            "conv_min_s": min_s}
