"""The ``voxel_bev`` family (SECOND, cell ``kitti.fresh-c4``): found as
files, a whole run on the CPU at a tiny size correct and its planted
fault and control not, its reference against the program's own, its
traffic and work pinned, and the dense stage's count by hand."""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import shutil

import numpy as np
import pytest

import faults
import run
import scenes
import voxel_bev_counts
import voxel_bev_reference as bev

CELL = "kitti.fresh-c4"
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
#: a 6.4 m x 6.4 m box in front of the sensor, second.yaml's layers at
#: narrow widths
TINY_BOX = [0.0, -3.2, -3.0, 6.4, 3.2, 1.0]
TINY_ARCH = {"sparse_shape_zyx": [41, 128, 128], "stem": [8, 8],
             "bev": {"layer_nums": [1, 1], "layer_strides": [1, 2],
                     "num_filters": [16, 32], "upsample_strides": [1, 2],
                     "num_upsample_filters": [16, 16]},
             "range_m": TINY_BOX}
#: sha256 of the mix's first 8 requests for two seeds, and of every
#: base scene's cloud_work, 10 scenes a chunk
REQUESTS = {
    2**31 + 5:
    "ffa7f9a3fbfa0c80651f4076486dac1d108811ab0eb2f4d9cf0aa09edf532340",
    2**40 + 77:
    "2630547bdd4914fc993426da4bb49261b524735003f0bd73ebf5d5ec831f67ba"}
WORK = ["1a4876847f8c44c4a75dfde9f1ff82e02043dfcc49f82aa01fd351346b25fbd8",
        "fdb173a5e4c5b025da69268f5a9be30a4add3544f13f4079658e0e53e8a82a3e",
        "0c2b5b063d97f661e98fe99eb84ae30816adef7a080caf0cf87160e09a84eb58",
        "a27d3aabe8794076c0d969681064f4c90caa02fae58b1156c918642397509f72",
        "4e6183b5e15dac217791bb6b75ec79b8cdd18ff80890165eeb81b8ab5f15400a",
        "31c2503d32ddc71b080253f072b1c0575ca5b22181dfec75e29616a448cc1d23",
        "5702944de1dacc9ac3833ed8291eb3702bc88d1080ffdae4916c97d62256842a",
        "8c0fedd24354662ad78660cf864329875015fe921540e4d0fc5daf7bf26efa6e"]


@pytest.fixture(scope="module")
def spec():
    return run.cell_spec(run.ROOT, CELL)


def tiny_spec(root=run.ROOT):
    s = run.cell_spec(str(root), CELL)
    cfg = s["config"]
    s["config"] = dict(cfg, **TINY_ARCH, stages=[
        dict(st, out=w) for st, w in zip(cfg["stages"], (8, 16, 16, 16))])
    t = s["traffic"]
    s["traffic"] = dict(t, params=dict(t["params"], rings=32, az_steps=512,
                                       range_m=TINY_BOX),
                        extent_voxels=[128, 128, 48], bucket=2048, pool=3)
    return s


def test_family_is_found_as_files(spec):
    fam = spec["family"]
    assert fam.__file__.endswith("families/voxel_bev.py")
    assert all(hasattr(fam, h) for h in run.FAMILY_HOOKS
               + ("control", "rehearse"))
    a = fam.arch(spec["config"])
    assert a.sparse_shape == (1408, 1600, 41)
    assert bev.extents(a)[1:] == [(704, 800, 21), (352, 400, 11),
                                  (176, 200, 5), (176, 200, 2)]
    assert bev.bev_hwc(a) == (200, 176, 256)
    assert spec["traffic"]["transforms"] == ["flip_y"]
    names = {m["name"] for m in spec["per_layer"]}
    assert names == {"bev_dense_ms", "bev_dense_roofline", "bev_occupancy"}


@pytest.mark.parametrize("fault", [None, "alter_answer", "control"])
def test_tiny_run(tmp_path, monkeypatch, capsys, fault):
    # the traced run writes and reads its trace under its own checkout
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    s = tiny_spec(tmp_path)
    monkeypatch.setattr(run, "device_peaks", lambda kind: PEAKS)
    fam = s["family"]
    hook = {None: None, "alter_answer": faults.alter_answer(),
            "control": fam.control(fam.arch(s["config"]))}[fault]
    out = run.run_cell(s, 2**33 + 9, 1.0, fault is None,
                       require_chip=False, on_engine=hook)
    err = out["checks"]["max_rel_err"]
    if fault is None:
        assert out["correct"], out["checks"]
        assert out["failed"] == 0 and out["attempted"] >= 4
        # every shape was compiled in the warm-up
        assert "unanswered; 0 compiles;" in capsys.readouterr().err
        # no device trace on the CPU: the device metrics stay silent
        assert set(out["metrics"]) == {"bev_occupancy"}
        assert 0 < out["metrics"]["bev_occupancy"]["value"] < 200
    else:
        assert not out["correct"] and err["value"] > err["limit"], err


def test_reference_agrees_with_program_reference():
    import jax
    from repro.models import second, second_reference

    s = tiny_spec()
    fam = s["family"]
    a = fam.arch(s["config"])
    coords, feats = scenes.base_scene(s["traffic"], 11)
    params = fam.init_params(a, 2**34 + 1)
    want = fam.reference(a, params, coords, feats, 2048)
    cfg = second.SECONDConfig(
        in_ch=a.in_ch, sparse_shape=a.sparse_shape, stem=a.stem,
        stages=a.stages, bev_layers=a.bev_layers, bev_strides=a.bev_strides,
        bev_filters=a.bev_filters, up_strides=a.up_strides,
        up_filters=a.up_filters, bn_eps=a.bn_eps)
    got = second_reference.forward(jax.device_get(params), coords, feats, cfg)
    assert got.shape == want.shape == (256, 72)
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


def _digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()


@pytest.mark.parametrize("seed", sorted(REQUESTS))
def test_requests_are_pinned(spec, seed):
    traffic = dict(spec["traffic"], pool=2)
    pool = scenes.base_pool(traffic)
    reqs = [(i, c.tolist(), f.round(6).tolist()) for i, c, f in
            itertools.islice(scenes.requests(pool, traffic, seed), 8)]
    assert _digest(reqs) == REQUESTS[seed]


@pytest.mark.parametrize("chunk", range(8))
def test_base_scene_work_is_pinned(spec, chunk):
    traffic = spec["traffic"]
    per = traffic["pool"] // len(WORK)
    fam = spec["family"]
    a = fam.arch(spec["config"])
    works = []
    for k in range(chunk * per, (chunk + 1) * per):
        # base_pool redraws only scenes past the bucket, so scene k of the
        # pool is scene pool_seed + k
        c, _ = scenes.base_scene(traffic, traffic["pool_seed"] + k)
        assert 19_000 < c.shape[0] <= traffic["bucket"]
        works.append(fam.cloud_work(a, c, PEAKS))
    assert _digest(works) == WORK[chunk]


def test_dense_stage_is_counted_by_hand(spec):
    a = bev.arch(spec["config"])
    cells, half = 200 * 176, 100 * 88
    flops = (2 * cells * 9 * 256 * 128 + 5 * 2 * cells * 9 * 128 * 128
             + 2 * half * 9 * 128 * 256 + 5 * 2 * half * 9 * 256 * 256
             + 2 * cells * 128 * 256 + 2 * cells * 256 * 256
             + 2 * cells * 512 * (18 + 42 + 12))
    assert flops == 139_277_107_200
    dense = voxel_bev_counts.dense_work(a)
    assert len(dense) == 12 + 2 + 1
    assert sum(d["flops"] for d in dense) == flops
    peaks = run.device_peaks("TPU v5 lite")
    c, _ = scenes.base_scene(spec["traffic"], spec["traffic"]["pool_seed"])
    work = voxel_bev_counts.cloud_work(a, c, peaks)
    assert work["bev_min_s"] == flops / peaks["flops_per_s"]
    assert sum(d["bytes"] for d in dense) / peaks["bytes_per_s"] \
        < work["bev_min_s"]
    assert work["model_flops"] == work["conv_flops"] + flops
    assert work["bev_cells"] == cells
