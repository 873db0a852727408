"""Small cells for the benchmark's own CPU tests."""
from __future__ import annotations

import os

import run

TINY_ARCH = {"stem": 8, "enc": [8, 16], "dec": [16, 8], "blocks": 1,
             "classes": 4}
TINY_TRAFFIC = {"params": {"n_points": 4000, "room_min_m": 2.2,
                           "room_max_m": 2.3, "height_m": 1.5,
                           "voxel_m": 0.05},
                "extent_voxels": [48, 48, 32], "bucket": 4096, "pool": 3}


def tiny_spec(workload: str = "scannet.fresh-c4") -> dict:
    """The cell's spec with a tiny model and small indoor rooms, so that a
    whole run fits a CPU test."""
    spec = run.cell_spec(run.ROOT, workload)
    spec["config"] = dict(spec["config"], **TINY_ARCH)
    spec["traffic"] = dict(spec["traffic"], **TINY_TRAFFIC)
    return spec


def cpu_env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env
