"""The harness finds a new configuration, traffic mix, per-layer metric
and model family by name, refuses a configuration without a family, and
refuses to run without a TPU."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import faults
import run
import scenes
import testkit

#: a model family that is not the benchmark's own: a per-voxel linear map
#: served by a toy engine, with its reference, work counts and control
TOY_FAMILY = '''
import types

import jax
import jax.numpy as jnp
import numpy as np

kernels = ()


def arch(cfg):
    return (int(cfg["in_ch"]), int(cfg["classes"]))


def init_params(a, seed):
    return jax.random.normal(jax.random.key(seed & 0xFFFFFFFF), a)


class Engine:
    def __init__(self, params):
        self.params, self.results, self._queue = params, [], []

    def submit(self, rid, coords, batch, valid, feats, *, deadline_s=None):
        self._queue.append((rid, valid, feats))

    def _build(self, valid):
        return int(np.sum(valid))

    def _forward_fn(self, params, feats):
        return jnp.dot(feats, params, precision="highest")

    def step(self):
        out = []
        for rid, valid, feats in self._queue:
            self._build(valid)
            y = np.asarray(self._forward_fn(self.params, jnp.asarray(feats)))
            out.append(types.SimpleNamespace(rid=rid, status="completed",
                                             degraded=False, answer=y))
        self._queue = []
        self.results += out
        return out


def serve(a, cfg, params, *, bucket, clients, impl):
    engine = Engine(params)
    return {"engine": engine, "plan_build": (engine, "_build"),
            "dispatch": (engine, "_forward_fn")}


def answer(result):
    return result.answer


def reference(a, params, coords, feats, bucket, *, precision="highest"):
    w = np.asarray(params, np.float64)
    if precision != "highest":
        w = np.asarray(jnp.asarray(params).astype(jnp.bfloat16), np.float64)
    return (np.asarray(feats, np.float64) @ w).astype(np.float32)


def cloud_work(a, coords, peaks):
    macs = coords.shape[0] * a[0] * a[1]
    return {"conv_flops": 0, "model_flops": 2 * macs, "conv_min_s": 0.0,
            "toy_macs": macs}


def control(a):
    def hook(engine):
        def forward_fn(params, feats):
            return jnp.asarray(reference(a, params, None, np.asarray(feats),
                                         0, precision="bf16"))
        engine._forward_fn = forward_fn
    return hook
'''


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _checkout(tmp_path):
    """A copy of the benchmark, and the digests of its files."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(run.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    return root, _digest(root / "bench")


def _config_file(root, workload):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    config = next(w["config"] for w in spec["workloads"]
                  if w["name"] == workload)
    return root / next(c["file"] for c in spec["configs"]
                       if c["name"] == config)


def test_new_files_are_found_by_name(tmp_path):
    root, before = _checkout(tmp_path)
    bench = root / "bench"
    cfg = json.loads(_config_file(root, "scannet.fresh-c4").read_text())
    cfg.update(testkit.TINY_ARCH, name="tiny-unet", classes=5)
    (bench / "configs" / "tiny-unet.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "indoor-fresh-c4.json")
                         .read_text())
    traffic.update(testkit.TINY_TRAFFIC, clients=2)
    (bench / "traffic" / "tiny-rooms.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "clouds_seen.py").write_text(
        "def read(ctx):\n    return ctx['clouds']\n")
    (bench / "metrics" / "never_there.py").write_text(
        "def read(ctx):\n    return None\n")

    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-unet", "source": "test",
                            "file": "bench/configs/tiny-unet.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.rooms", "config": "tiny-unet",
                              "traffic": "tiny-rooms", "chips": 1,
                              "why": "test"})
    for m in spec["per_layer"]:
        if m["name"] in ("searches_per_cloud", "compiles_in_window"):
            m["workloads"].append("tiny.rooms")
    for name in ("clouds_seen", "never_there"):
        spec["per_layer"].append({"name": name, "unit": "count",
                                  "better": "higher",
                                  "source": "program_counter",
                                  "layer": "serve engine",
                                  "moves": "clouds_per_s",
                                  "workloads": ["tiny.rooms"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = run.cell_spec(str(root), "tiny.rooms")
    assert cell["config"]["classes"] == 5 and cell["traffic"]["clients"] == 2
    out = run.run_cell(cell, 2**35 + 1, 1.0, True, require_chip=False)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["clouds_seen"] == {"value": float(out["attempted"]),
                                "unit": "count"}
    assert "never_there" not in m
    assert m["searches_per_cloud"]["value"] == 5.0    # 3 subm + 2 down
    assert m["compiles_in_window"]["value"] == 0.0
    assert list(out)[-1] == "checks"
    assert {k: v for k, v in _digest(root / "bench").items()
            if k in before} == before


def _add_toy_cell(root):
    """Files and entries, and nothing else, for a cell of the toy family:
    its module, configuration, traffic mix and a reader of its own
    count."""
    bench = root / "bench"
    (bench / "families" / "toy.py").write_text(TOY_FAMILY)
    (bench / "configs" / "toy.json").write_text(json.dumps({
        "name": "toy", "family": "toy", "in_ch": 4, "classes": 3,
        "voxel_m": 0.05, "check": {"max_rel_err": 1e-5}}))
    traffic = json.loads((bench / "traffic" / "indoor-fresh-c4.json")
                         .read_text())
    traffic.update(testkit.TINY_TRAFFIC, clients=2)
    (bench / "traffic" / "toy-rooms.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "toy_macs.py").write_text(
        "def read(ctx):\n    return ctx['work']['toy_macs'] / ctx['clouds']\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "test",
                            "file": "bench/configs/toy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy.rooms", "config": "toy",
                              "traffic": "toy-rooms", "chips": 1,
                              "why": "test"})
    for m in spec["per_layer"]:
        if m["name"] == "plan_build_ms":
            m["workloads"].append("toy.rooms")
    spec["per_layer"].append({"name": "toy_macs", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "whole forward",
                              "moves": "clouds_per_s",
                              "workloads": ["toy.rooms"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("fault", [None, "alter_answer", "control"])
def test_new_family_is_added_as_files(tmp_path, monkeypatch, fault):
    root, before = _checkout(tmp_path)
    _add_toy_cell(root)
    peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    monkeypatch.setattr(run, "device_peaks", lambda kind: peaks)
    cell = run.cell_spec(str(root), "toy.rooms")
    fam = cell["family"]
    hook = {None: None, "alter_answer": faults.alter_answer(),
            "control": fam.control(fam.arch(cell["config"]))}[fault]
    out = run.run_cell(cell, 2**36 + 5, 1.0, True, require_chip=False,
                       on_engine=hook)
    err = out["checks"]["max_rel_err"]
    if fault is None:
        assert out["correct"], out["checks"]
        assert out["failed"] == 0 and out["attempted"] >= 2
        m = out["metrics"]
        assert m["plan_build_ms"]["value"] > 0
        # every cloud of the window is one of the pool's rooms
        sizes = [c.shape[0] for c, _ in scenes.base_pool(cell["traffic"])]
        assert min(sizes) * 12 <= m["toy_macs"]["value"] <= max(sizes) * 12
    else:
        assert not out["correct"] and err["value"] > err["limit"], err
    assert {k: v for k, v in _digest(root / "bench").items()
            if k in before} == before


@pytest.mark.parametrize("family", [None, "", "second", "../run"])
def test_configuration_without_a_family_module_is_refused(tmp_path, family):
    root, _ = _checkout(tmp_path)
    path = _config_file(root, "scannet.fresh-c4")
    cfg = json.loads(path.read_text())
    cfg.pop("family")
    if family is not None:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    with pytest.raises(SystemExit) as e:
        run.cell_spec(str(root), "scannet.fresh-c4")
    msg = str(e.value)
    assert repr(cfg["name"]) in msg
    assert (repr(family) if family else "no model family") in msg


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(run.ROOT, "bench", "run.py"),
         "--workload", "scannet.fresh-c4", "--seed", "3000000000",
         "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=testkit.cpu_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr
