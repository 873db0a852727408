"""Neighbour search and work counts against brute force, the invariance
of the work under the fresh-traffic transforms, the cells' request
streams and work pinned to digests, and the scene options a fixed-grid
model needs."""
from __future__ import annotations

import hashlib
import itertools
import json
import os

import numpy as np
import pytest

import counts
import geometry
import reference
import run
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


#: sha256 of the first 8 requests (base index, coords, feats) of each mix
#: for two seeds, and of the family's ``cloud_work`` of every base scene
#: of each cell, as the benchmark produced them before model families
#: (the 16-scene pools at the cells' real sizes)
STREAMS = {
    ("indoor-fresh-c4", 2**31 + 11):
        "464a3177e19c0ae030cb4da7e1c59d251d0a15d7b6b218987a2664fd62ea011a",
    ("indoor-fresh-c4", 2**40 + 3):
        "501ebccdc503a37415455909b07cc5b6e0924813ea2672e8e02ea93c2762f143",
    ("lidar-fresh-c4", 2**31 + 11):
        "3be32d81557d8e8741218c24cc08e131ad4a40d87eefe6c446c573facee751d7",
    ("lidar-fresh-c4", 2**40 + 3):
        "6c14efdd4dfb8466bc025731fa5442cf1603f85d30a301f38799a4f88923eefc",
    ("indoor-fresh-c1", 2**31 + 11):
        "464a3177e19c0ae030cb4da7e1c59d251d0a15d7b6b218987a2664fd62ea011a",
    ("indoor-fresh-c1", 2**40 + 3):
        "501ebccdc503a37415455909b07cc5b6e0924813ea2672e8e02ea93c2762f143",
}
WORK = {
    "scannet.fresh-c4":
        "3029cd5e14b63723a7de2e572054b075b9448cb1d72f8176a636a4a122cda93f",
    "semkitti.fresh-c4":
        "bb2dc84d08e285436969a8b69ac3bb648dcb389d83f7458888631d14088a72b8",
    "scannet.fresh-c1":
        "3029cd5e14b63723a7de2e572054b075b9448cb1d72f8176a636a4a122cda93f",
}


@pytest.mark.parametrize("mix,seed", list(STREAMS))
def test_request_stream_is_pinned(mix, seed):
    traffic = run.load_json(os.path.join(run.HERE, "traffic", mix + ".json"))
    pool = scenes.base_pool(traffic)
    h = hashlib.sha256()
    for i, c, f in itertools.islice(scenes.requests(pool, traffic, seed), 8):
        h.update(str(i).encode())
        h.update(np.ascontiguousarray(c).tobytes())
        h.update(np.ascontiguousarray(f).tobytes())
    assert h.hexdigest() == STREAMS[mix, seed]


@pytest.mark.parametrize("workload", list(WORK))
def test_cloud_work_is_pinned(workload):
    spec = run.cell_spec(run.ROOT, workload)
    fam = spec["family"]
    peaks = run.device_peaks("TPU v5 lite")
    a = fam.arch(spec["config"])
    works = [fam.cloud_work(a, c, peaks)
             for c, _ in scenes.base_pool(spec["traffic"])]
    assert hashlib.sha256(json.dumps(works).encode()).hexdigest() \
        == WORK[workload]


INDOOR = {"generator": "indoor",
          "params": {"n_points": 3000, "room_min_m": 2.2, "room_max_m": 2.3,
                     "height_m": 1.5, "voxel_m": 0.05},
          "extent_voxels": [48, 48, 32], "pool": 3, "pool_seed": 0,
          "bucket": 4096}
#: SECOND's KITTI box and voxels (OpenPCDet ``second.yaml``) over a
#: sparser sweep than a 64-ring sensor's
BOX = {"generator": "lidar",
       "params": {"rings": 32, "az_steps": 512, "elev_min_deg": -25.0,
                  "elev_max_deg": 3.0, "max_range_m": 120.0,
                  "sensor_height_m": 1.73, "boxes": [8, 24],
                  "range_m": [0.0, -40.0, -3.0, 70.4, 40.0, 1.0],
                  "voxel_m": [0.05, 0.05, 0.1]},
       "extent_voxels": [1408, 1600, 48], "pool": 2, "pool_seed": 0,
       "bucket": 65536}


def _inside_voxels(coords, feats, voxel, ext):
    assert coords.min() >= 0 and np.all(coords < np.asarray(ext))
    off = feats[:, :3]
    assert np.all(off >= -1e-6) and np.all(off < np.asarray(voxel) + 1e-6)


def test_scalar_voxel_equals_the_same_edge_per_axis():
    c, f = scenes.base_scene(INDOOR, 4)
    per_axis = dict(INDOOR, params=dict(INDOOR["params"],
                                        voxel_m=[0.05] * 3))
    d, g = scenes.base_scene(per_axis, 4)
    assert np.array_equal(c, d) and np.array_equal(f, g)


def test_per_axis_voxels_hold_their_points():
    traffic = dict(INDOOR, params=dict(INDOOR["params"],
                                       voxel_m=[0.05, 0.1, 0.2]))
    c, f = scenes.base_scene(traffic, 4)
    c0, _ = scenes.base_scene(INDOOR, 4)
    _inside_voxels(c, f, [0.05, 0.1, 0.2], traffic["extent_voxels"])
    assert 0 < c.shape[0] < c0.shape[0]
    assert c[:, 1].max() < c0[:, 1].max() and c[:, 2].max() < c0[:, 2].max()


def test_range_box_crops_the_sweep():
    c, f = scenes.base_scene(BOX, 0)
    _inside_voxels(c, f, [0.05, 0.05, 0.1], BOX["extent_voxels"])
    lo, hi = np.split(np.asarray(BOX["params"]["range_m"]), 2)
    centre = lo + (c + 0.5) * np.asarray([0.05, 0.05, 0.1])
    assert np.all(centre > lo) and np.all(centre < hi)
    assert c.shape[0] > 1000 and c[:, 2].max() < 40      # 4 m of 0.1 m


def test_flip_y_only_stays_in_place():
    traffic = dict(BOX, transforms=["flip_y"])
    pool = scenes.base_pool(traffic)
    ext = np.asarray(traffic["extent_voxels"])
    seen = set()
    for i, c, f in itertools.islice(scenes.requests(pool, traffic, 2**33),
                                    16):
        base = pool[i][0]
        mirrored = scenes.transform(base, ext, 2, (0, 0, 0))
        assert c.min() >= 0 and np.all(c < ext)
        assert np.array_equal(c[:, [0, 2]], base[:, [0, 2]])
        flipped = np.array_equal(c, mirrored)
        assert flipped or np.array_equal(c, base)
        assert np.array_equal(f, pool[i][1])
        seen.add((i, flipped))
    assert len(seen) > len(pool)            # both sides of the mirror drawn


def test_unknown_transform_is_refused():
    pool = scenes.base_pool(INDOOR)
    with pytest.raises(ValueError, match="rotate"):
        next(scenes.requests(pool, dict(INDOOR, transforms=["rotate"]), 1))
