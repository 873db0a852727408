"""The harness finds a new configuration, traffic mix and per-layer metric
by name, and refuses to run without a TPU."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import run
import testkit


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(run.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    before = _digest(root / "bench")

    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "minkunet14a-scannet.json")
                     .read_text())
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
