"""Plain numpy neighbour search over a voxel cloud, for the reference and
the work counts. Shares nothing with the program's map search.

``hierarchy(coords, levels)`` gives, for the cloud and each of its
``levels`` stride-2 coarsenings, the voxels and three gather-form maps
(row ``i`` of a map lists, per kernel tap, the input row that feeds
output ``i``, or -1):

* ``subm[r]``  (N_r, 27): the 3x3x3 neighbours of level r in level r,
  tap ``(dx+1) + 3(dy+1) + 9(dz+1)``;
* ``down[r]``  (N_{r+1}, 8): the children in level r of each level r+1
  voxel, tap = child octant ``(x&1) | (y&1)<<1 | (z&1)<<2``;
* ``up[r]``    (N_r, 8): the parent in level r+1 of each level r voxel,
  in the column of its own octant.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

OFFSETS = np.array([(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
                    for dx in (-1, 0, 1)], np.int64)


class Level(NamedTuple):
    coords: np.ndarray     # (N_r, 3) int64
    subm: np.ndarray       # (N_r, 27) int32


class Hierarchy(NamedTuple):
    levels: list           # Level per resolution 0..L
    down: list             # (N_{r+1}, 8) int32 per r < L
    up: list               # (N_r, 8) int32 per r < L


def _keys(c: np.ndarray) -> np.ndarray:
    c = c.astype(np.int64) + 1          # room for the -1 neighbour offset
    return (c[:, 0] << 42) | (c[:, 1] << 21) | c[:, 2]


def lookup(table: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Row of each query coordinate in ``table`` (unique rows), or -1."""
    kt = _keys(table)
    order = np.argsort(kt, kind="stable")
    ks = kt[order]
    kq = _keys(queries)
    pos = np.minimum(np.searchsorted(ks, kq), len(ks) - 1)
    hit = ks[pos] == kq
    return np.where(hit, order[pos], -1).astype(np.int32)


def subm_map(coords: np.ndarray) -> np.ndarray:
    c = coords.astype(np.int64)
    q = (c[:, None, :] + OFFSETS[None]).reshape(-1, 3)
    return lookup(c, q).reshape(c.shape[0], 27)


def coarsen(coords: np.ndarray):
    """Parents of a level: ``(parent coords, parent row of each child,
    octant of each child)``. Parents are listed in order of first
    appearance among the children."""
    c = coords.astype(np.int64)
    par = c >> 1
    _, first, inv = np.unique(_keys(par), return_index=True,
                              return_inverse=True)
    rank = np.empty(len(first), np.int64)
    rank[np.argsort(first, kind="stable")] = np.arange(len(first))
    pidx = rank[inv.reshape(-1)]
    pcoords = par[np.sort(first)]
    octant = (c[:, 0] & 1) | ((c[:, 1] & 1) << 1) | ((c[:, 2] & 1) << 2)
    return pcoords, pidx.astype(np.int32), octant.astype(np.int32)


def hierarchy(coords: np.ndarray, levels: int) -> Hierarchy:
    lv = [Level(coords.astype(np.int64), subm_map(coords))]
    down, up = [], []
    for _ in range(levels):
        child = lv[-1].coords
        pcoords, pidx, octant = coarsen(child)
        d = np.full((pcoords.shape[0], 8), -1, np.int32)
        d[pidx, octant] = np.arange(child.shape[0], dtype=np.int32)
        u = np.full((child.shape[0], 8), -1, np.int32)
        u[np.arange(child.shape[0]), octant] = pidx
        down.append(d)
        up.append(u)
        lv.append(Level(pcoords, subm_map(pcoords)))
    return Hierarchy(lv, down, up)
