"""Scene generators and the "fresh" request stream of the benchmark.

The generators are copies of the scene generators the program ships
(indoor rooms and ring-structured LiDAR sweeps), kept here so that a
change to the program cannot change the benchmark's inputs. Unlike the
program's ``make_batch`` they never thin a scene: every voxel a scene
occupies is served.

A traffic file names a generator and its parameters. The base pool is a
fixed set of scenes (the same for every ``--seed``, so every run serves
the same sizes); ``--seed`` draws the order in which clients send them
and a fresh transform for every request: by default an x/y flip or swap
and a translation by whole 16-voxel octree blocks. No two requests share
geometry, yet each does exactly the work of its base scene, because
stride-2 coarsening commutes with 16-voxel shifts and with flips of an
extent that is a multiple of 16.

A mix may instead list the transforms it draws (``"transforms"``, out of
``TRANSFORMS``), in the same draw order. A model whose input grid is fixed
in absolute coordinates, such as a bird's-eye-view detector, lists only
``flip_y``: its requests then stay where the scene was voxelized.

``voxel_m`` is one edge for all three axes or a per-axis ``[x, y, z]``
list. The LiDAR generator crops either to ``crop_half_m`` around the
sensor above ``z_min_m``, or to an axis-aligned box ``range_m`` =
``[xmin, ymin, zmin, xmax, ymax, zmax]`` in metres.
"""
from __future__ import annotations

import numpy as np

#: voxel coordinates lie in [0, GRID) per axis (16-voxel blocks, 7 bits)
GRID = 2048
BLOCK = 16
#: the request transforms a mix may draw; bits 0-2 of a variant are the
#: first three, in this order
TRANSFORMS = ("flip_x", "flip_y", "swap", "shift")


def lidar_points(rng: np.random.Generator, *, n_rings: int, az_steps: int,
                 elev_min_deg: float, elev_max_deg: float,
                 max_range: float, sensor_height: float,
                 n_boxes: tuple) -> np.ndarray:
    """(P, 4) points x, y, z, intensity of one ring-structured sweep over
    a ground plane with random boxes (cars, poles) intercepting rays."""
    elev = np.deg2rad(np.linspace(elev_min_deg, elev_max_deg, n_rings))
    az = np.linspace(-np.pi, np.pi, az_steps, endpoint=False)
    elev_g, az_g = np.meshgrid(elev, az, indexing="ij")
    with np.errstate(divide="ignore"):
        r_ground = np.where(np.sin(elev_g) < -1e-3,
                            sensor_height / -np.sin(elev_g), max_range)
    r = np.minimum(r_ground, max_range)
    for _ in range(int(rng.integers(n_boxes[0], n_boxes[1]))):
        cx, cy = rng.uniform(-40, 40, 2)
        w, l, h = rng.uniform(0.5, 4.0, 3)
        az_c = np.arctan2(cy, cx)
        dist = np.hypot(cx, cy)
        half_ang = np.arctan2(max(w, l) / 2, dist)
        hit = (np.abs(((az_g - az_c + np.pi) % (2 * np.pi)) - np.pi)
               < half_ang)
        z_at = dist * np.sin(elev_g)
        hit &= (z_at > -sensor_height) & (z_at < -sensor_height + h)
        r = np.where(hit & (dist < r), dist, r)
    keep = r < max_range
    x = (r * np.cos(elev_g) * np.cos(az_g))[keep]
    y = (r * np.cos(elev_g) * np.sin(az_g))[keep]
    z = (r * np.sin(elev_g))[keep]
    inten = rng.uniform(0, 1, x.shape[0])
    return np.stack([x, y, z, inten], axis=1)


def indoor_points(rng: np.random.Generator, *, n_points: int, room: float,
                  height: float) -> np.ndarray:
    """(P, 4) points sampled from a room's floor, walls and furniture."""
    pts = []
    n_floor = n_points // 3
    pts.append(np.column_stack([rng.uniform(0, room, (n_floor, 2)),
                                np.zeros(n_floor)]))
    n_wall = n_points // 3
    side = rng.integers(0, 4, n_wall)
    u = rng.uniform(0, room, n_wall)
    v = rng.uniform(0, height, n_wall)
    wx = np.where(side <= 1, u, np.where(side == 2, 0.0, room))
    wy = np.where(side == 0, 0.0, np.where(side == 1, room, u))
    pts.append(np.column_stack([wx, wy, v]))
    n_obj = n_points - n_floor - n_wall
    n_boxes = int(rng.integers(4, 10))
    per = n_obj // n_boxes
    for _ in range(n_boxes):
        c = rng.uniform(1, room - 1, 2)
        s = rng.uniform(0.3, 1.5, 3)
        pts.append(rng.uniform(-0.5, 0.5, (per, 3)) * s
                   + [c[0], c[1], s[2] / 2])
    pts = np.concatenate(pts)
    inten = rng.uniform(0, 1, pts.shape[0])
    return np.column_stack([pts, inten])


def voxelize(points: np.ndarray, voxel, origin, extent):
    """Every occupied voxel of ``points`` inside ``[0, extent)`` per axis:
    ``(coords (V, 3) int32, feats (V, 4) float32)`` with per-voxel mean
    features (offset inside the voxel, intensity). ``voxel`` is one edge
    or one per axis. Never thins."""
    origin = np.asarray(origin, np.float64)
    voxel = np.asarray(voxel, np.float64)
    ijk = np.floor((points[:, :3] - origin) / voxel).astype(np.int64)
    ok = np.all((ijk >= 0) & (ijk < np.asarray(extent)), axis=1)
    ijk, pts = ijk[ok], points[ok]
    key = (ijk[:, 0] << 22) | (ijk[:, 1] << 11) | ijk[:, 2]
    order = np.argsort(key, kind="stable")
    key_s, ijk_s, pts_s = key[order], ijk[order], pts[order]
    new = np.concatenate([[True], key_s[1:] != key_s[:-1]])
    vid = np.cumsum(new) - 1
    n_vox = int(vid[-1]) + 1 if len(vid) else 0
    coords = ijk_s[new].astype(np.int32)
    cnt = np.maximum(np.bincount(vid, minlength=n_vox), 1)
    feats = np.stack([np.bincount(vid, weights=pts_s[:, c], minlength=n_vox)
                      for c in range(4)], axis=1) / cnt[:, None]
    feats[:, :3] -= coords * voxel + origin
    return coords, feats.astype(np.float32)


def _extent(traffic: dict) -> np.ndarray:
    ext = np.asarray(traffic["extent_voxels"], np.int64)
    assert np.all(ext % BLOCK == 0) and np.all(ext <= GRID), ext
    return ext


def base_scene(traffic: dict, scene_seed: int):
    """One base scene of the traffic's generator, un-thinned."""
    p = traffic["params"]
    rng = np.random.default_rng(scene_seed)
    if traffic["generator"] == "indoor":
        room = float(rng.uniform(p["room_min_m"], p["room_max_m"]))
        pts = indoor_points(rng, n_points=p["n_points"], room=room,
                            height=p["height_m"])
        origin = (0.0, 0.0, 0.0)
    elif traffic["generator"] == "lidar":
        pts = lidar_points(rng, n_rings=p["rings"], az_steps=p["az_steps"],
                           elev_min_deg=p["elev_min_deg"],
                           elev_max_deg=p["elev_max_deg"],
                           max_range=p["max_range_m"],
                           sensor_height=p["sensor_height_m"],
                           n_boxes=tuple(p["boxes"]))
        if "range_m" in p:
            lo, hi = np.split(np.asarray(p["range_m"], np.float64), 2)
            pts = pts[np.all((pts[:, :3] >= lo) & (pts[:, :3] < hi), axis=1)]
            origin = tuple(lo)
        else:
            half = p["crop_half_m"]
            origin = (-half, -half, p["z_min_m"])
    else:
        raise ValueError(f"unknown generator {traffic['generator']!r}")
    return voxelize(pts, p["voxel_m"], origin, _extent(traffic))


def base_pool(traffic: dict) -> list:
    """The traffic's fixed pool of base scenes. A scene larger than the
    bucket is redrawn from the next scene seed, never thinned."""
    pool, s = [], int(traffic["pool_seed"])
    while len(pool) < traffic["pool"]:
        if s > int(traffic["pool_seed"]) + 10 * traffic["pool"]:
            raise ValueError(f"scenes keep exceeding the bucket "
                             f"{traffic['bucket']}: raise it")
        c, f = base_scene(traffic, s)
        s += 1
        if c.shape[0] <= traffic["bucket"]:
            pool.append((c, f))
    return pool


def transform(coords: np.ndarray, ext: np.ndarray, variant: int,
              shift_blocks) -> np.ndarray:
    """Flip x (bit 0), flip y (bit 1), swap x/y (bit 2), then translate by
    whole blocks. Flips mirror within the extent (a multiple of 16)."""
    c = coords.astype(np.int64).copy()
    if variant & 1:
        c[:, 0] = ext[0] - 1 - c[:, 0]
    if variant & 2:
        c[:, 1] = ext[1] - 1 - c[:, 1]
    if variant & 4:
        c[:, [0, 1]] = c[:, [1, 0]]
    c += BLOCK * np.asarray(shift_blocks, np.int64)
    return c.astype(np.int32)


def requests(pool: list, traffic: dict, seed: int):
    """Endless seeded stream of ``(base_index, coords, feats)``: the pool
    in a fresh random order each round, each request under a fresh
    transform drawn from the mix's ``transforms`` (all of ``TRANSFORMS``
    where it lists none; a swap only on a square extent). Same seed, same
    stream."""
    drawn = traffic.get("transforms", TRANSFORMS)
    unknown = set(drawn) - set(TRANSFORMS)
    if unknown:
        raise ValueError(f"unknown transforms {sorted(unknown)}; "
                         f"known: {TRANSFORMS}")
    rng = np.random.default_rng(seed)
    ext = _extent(traffic)
    bits = [b for b, name in enumerate(TRANSFORMS[:3]) if name in drawn
            and (name != "swap" or ext[0] == ext[1])]
    span = (GRID - ext) // BLOCK if "shift" in drawn else None
    while True:
        for i in rng.permutation(len(pool)):
            c, f = pool[i]
            k = int(rng.integers(0, 1 << len(bits))) if bits else 0
            v = sum(((k >> j) & 1) << b for j, b in enumerate(bits))
            shift = ([int(rng.integers(0, s + 1)) for s in span]
                     if span is not None else (0, 0, 0))
            yield int(i), transform(c, ext, v, shift), f


def padded(coords: np.ndarray, feats: np.ndarray, bucket: int):
    """A request's arrays padded to ``bucket`` rows, valid rows first."""
    n = coords.shape[0]
    c = np.zeros((bucket, 3), np.int32)
    f = np.zeros((bucket, feats.shape[1]), np.float32)
    v = np.zeros((bucket,), bool)
    c[:n], f[:n], v[:n] = coords, feats, True
    return c, np.zeros((bucket,), np.int32), v, f
