"""Neighbour search and work counts against brute force, and the
invariance of the work under the fresh-traffic transforms."""
from __future__ import annotations

import itertools

import numpy as np
import pytest

import counts
import geometry
import reference
import scenes

ARCH = reference.Arch(in_ch=4, stem=8, enc=(8, 16, 16), dec=(16, 8, 8),
                      blocks=2, classes=3)
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


def small_cloud(seed=0, n=300, side=12):
    rng = np.random.default_rng(seed)
    c = rng.integers(0, side, (n, 3))
    return np.unique(c, axis=0).astype(np.int32)


def test_subm_map_matches_brute_force():
    c = small_cloud()
    got = geometry.subm_map(c)
    where = {tuple(x): i for i, x in enumerate(c.tolist())}
    for i, x in enumerate(c):
        for t, d in enumerate(geometry.OFFSETS):
            j = where.get(tuple((x + d).tolist()), -1)
            assert got[i, t] == j


def test_hierarchy_matches_brute_force():
    c = small_cloud(seed=1)
    h = geometry.hierarchy(c, 2)
    for r in range(2):
        child, parent = h.levels[r].coords, h.levels[r + 1].coords
        assert sorted(map(tuple, parent.tolist())) == sorted(
            set(map(tuple, (child >> 1).tolist())))
        for i, x in enumerate(child):
            octant = (x[0] & 1) | ((x[1] & 1) << 1) | ((x[2] & 1) << 2)
            p = h.up[r][i, octant]
            assert (parent[p] == x >> 1).all()
            assert (h.up[r][i] >= 0).sum() == 1
            assert h.down[r][p, octant] == i
        assert (h.down[r] >= 0).sum() == child.shape[0]


def test_conv_work_counts_by_brute_force():
    c = small_cloud(seed=2)
    h = geometry.hierarchy(c, len(ARCH.enc))
    work = counts.conv_work(ARCH, h)
    assert len(work) == len(reference.layers(ARCH))
    lv0 = {tuple(x) for x in c.tolist()}
    pairs = sum(tuple((np.array(x) + d).tolist()) in lv0
                for x in lv0 for d in geometry.OFFSETS)
    stem = work[0]
    assert stem["maps"] == pairs
    assert stem["flops"] == 2 * pairs * ARCH.in_ch * ARCH.stem
    assert stem["bytes"] == 4 * (len(lv0) * ARCH.in_ch
                                 + 27 * ARCH.in_ch * ARCH.stem
                                 + len(lv0) * ARCH.stem)
    down0 = work[1]
    assert down0["kind"] == "down" and down0["maps"] == len(lv0)


@pytest.mark.parametrize("variant", range(8))
def test_work_is_invariant_under_fresh_transforms(variant):
    traffic = {"generator": "indoor",
               "params": {"n_points": 6000, "room_min_m": 2.0,
                          "room_max_m": 2.5, "height_m": 1.5,
                          "voxel_m": 0.05},
               "extent_voxels": [64, 64, 32]}
    c, _ = scenes.base_scene(traffic, 5)
    ext = np.asarray(traffic["extent_voxels"])
    moved = scenes.transform(c, ext, variant, (3, 7, 11))
    assert moved.min() >= 0 and moved.max() < scenes.GRID
    assert not np.array_equal(np.sort(moved, 0), np.sort(c, 0))
    a = counts.cloud_work(ARCH, c, PEAKS)
    b = counts.cloud_work(ARCH, moved, PEAKS)
    assert a == b
    wa = counts.conv_work(ARCH, geometry.hierarchy(c, len(ARCH.enc)))
    wb = counts.conv_work(ARCH, geometry.hierarchy(moved, len(ARCH.enc)))
    assert wa == wb


def test_request_stream_is_seeded_and_never_repeats_geometry():
    traffic = {"generator": "indoor",
               "params": {"n_points": 3000, "room_min_m": 2.2,
                          "room_max_m": 2.3, "height_m": 1.5,
                          "voxel_m": 0.05},
               "extent_voxels": [48, 48, 32], "pool": 3, "pool_seed": 0,
               "bucket": 4096}
    pool = scenes.base_pool(traffic)
    first = list(itertools.islice(scenes.requests(pool, traffic, 2**40 + 3),
                                  12))
    again = list(itertools.islice(scenes.requests(pool, traffic, 2**40 + 3),
                                  12))
    for (i, c, f), (j, d, g) in zip(first, again):
        assert i == j and np.array_equal(c, d) and np.array_equal(f, g)
    keys = {c.tobytes() for _, c, _ in first}
    assert len(keys) == len(first)
    assert sorted(i for i, _, _ in first) == sorted(list(range(3)) * 4)
