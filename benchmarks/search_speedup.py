"""Fig. 9(a): map-search latency reduction (OCTENT algorithm + architecture).

Three complementary measurements per benchmark workload, written to
``BENCH_search.json`` (picked up by benchmarks/roofline.py --search):

  * cycle model (core.cyclemodel) — the paper's own evaluation method:
    serial hash baseline vs serial OCTENT vs 8-bank parallel OCTENT.
    Paper claims: >65 % (algo) + 66.7-68.3 % (arch) => 8.8-21.2x total.
  * search wall clock on this host — the fused OCTENT engine
    (kernels/octent: Pallas kernel under ops.hardware_impl, i.e. compiled
    on TPU / interpreted elsewhere, plus its XLA bit-oracle ``ref``)
    against the legacy dense-table ``xla`` builder and the serial
    host-side hash probing loop of [9]. On CPU the numbers demonstrate
    the *deserialization* win, not ASIC latency.
  * plan-build wall clock — the sort-free path (Morton-radix unique
    passes + closed-form counting tile layout) vs the retained global-
    argsort baseline, with the jaxpr sort-op audit attached. The
    acceptance claim is sort-free < argsort on every workload.

``--smoke`` (also wired into benchmarks/run.py --smoke and scripts/ci.sh)
runs the interpret-mode kernel on a tiny cloud with bit-exact parity
against the host hash oracle plus the sort-free audits, exiting nonzero
on any drift — the CI search-parity gate. It also spawns the 8-host-CPU-
device sharded gate (:func:`run_smoke_sharded`): sharded-vs-single kmap
parity on one small cloud over 2/8-way meshes plus the per-device
table-slice jaxpr audit (DESIGN.md §9).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import BENCHMARKS, csv_row, time_fn, workload
from repro.core import binning, cyclemodel, mapsearch, morton
from repro.kernels.octent import ops as oct_ops
from repro.kernels.spconv_gemm import ops as sg_ops

OUT_JSON = "BENCH_search.json"

# dataset-dependent hash probe factor (occupancy/collision regime): indoor
# scans are denser (longer chains), sweeping the paper's 8.8-21.2x band
PROBE = {"Seg(i)": 6.0, "Seg(o)": 3.4, "Det(k)": 2.6, "Det(n)": 3.0}


def _search_case(coords, batch, valid, *, max_blocks, kimpl, bm=128):
    """Timings + parity + audits for one coordinate set."""

    def kernel_path():
        return oct_ops.build_kmap(coords, batch, valid,
                                  max_blocks=max_blocks, impl=kimpl)[0]

    def ref_path():
        return oct_ops.build_kmap(coords, batch, valid,
                                  max_blocks=max_blocks, impl="ref")[0]

    def xla_path():
        return oct_ops.build_kmap(coords, batch, valid,
                                  max_blocks=max_blocks, impl="xla")[0]

    # plan-build comparison isolates the *binning* change: both sides run
    # the same octent ref search engine, differing only in the ordering
    # passes (radix counting vs the retained global argsorts)
    def plan_counting():
        kmap, _ = oct_ops.build_kmap(coords, batch, valid,
                                     max_blocks=max_blocks, impl="ref")
        return sg_ops.build_tap_tiles(kmap, bm=bm).gather_idx

    def plan_argsort():
        kmap, _ = oct_ops.build_kmap(coords, batch, valid,
                                     max_blocks=max_blocks, impl="ref",
                                     binning_mode="argsort")
        return sg_ops.build_tap_tiles(kmap, bm=bm,
                                      binning="argsort").gather_idx

    km_kernel = np.asarray(kernel_path())
    km_ref = np.asarray(ref_path())
    km_xla = np.asarray(xla_path())
    if not (km_kernel == km_ref).all() or not (km_kernel == km_xla).all():
        raise AssertionError("octent kmap parity drift across impls")

    sort_ops = {"counting": binning.sort_op_count(
                    plan_counting),
                "argsort": binning.sort_op_count(plan_argsort)}
    assert sort_ops["counting"] == 0, "sort-free plan build emitted a sort"
    assert sort_ops["argsort"] > 0, "argsort baseline lost its sort op"
    n = coords.shape[0]
    qt_audit = binning.avals_with_shape(kernel_path, shape=(n, 27, 3))
    assert qt_audit == 0, "fused path materialized the query tensor"

    rec = {
        "kernel_impl": kimpl,
        "search_us": {
            "octent_kernel": time_fn(kernel_path) * 1e6,
            "octent_ref": time_fn(ref_path) * 1e6,
            "xla_dense": time_fn(xla_path) * 1e6,
        },
        "plan_build_us": {
            "counting": time_fn(plan_counting) * 1e6,
            "argsort": time_fn(plan_argsort) * 1e6,
        },
        "sort_ops": sort_ops,
        "query_tensor_ops": qt_audit,
        "parity": True,
    }
    rec["search_speedup_vs_xla"] = (rec["search_us"]["xla_dense"]
                                    / rec["search_us"]["octent_kernel"])
    rec["plan_build_speedup"] = (rec["plan_build_us"]["argsort"]
                                 / rec["plan_build_us"]["counting"])
    return rec, km_kernel


def run(full: bool = True) -> list[str]:
    rows, records = [], []
    kimpl = oct_ops.hardware_impl()
    for name in BENCHMARKS:
        vb = workload(name)
        n = int(vb.valid.sum())
        lat = cyclemodel.search_cycles(n, probe_factor=PROBE[name])
        coords = jnp.asarray(vb.coords)
        batch = jnp.asarray(vb.batch)
        valid = jnp.asarray(vb.valid)
        rec, km = _search_case(coords, batch, valid,
                               max_blocks=vb.coords.shape[0], kimpl=kimpl)
        rec.update(workload=name, voxels=n,
                   cycle_model={
                       "algo_saving": lat.serial_algo_saving,
                       "arch_saving": lat.parallel_arch_saving,
                       "total_speedup": lat.total_speedup})
        if full:
            t0 = time.perf_counter()
            km_hash = mapsearch.build_kmap_hash(
                vb.coords, vb.batch, vb.valid,
                np.asarray(morton.subm3_offsets()))
            rec["search_us"]["host_hash"] = (time.perf_counter() - t0) * 1e6
            if not (km == km_hash).all():
                raise AssertionError(f"{name}: kmap drift vs hash oracle")
        records.append(rec)

        derived = (f"voxels={n};algo_saving={lat.serial_algo_saving:.3f};"
                   f"arch_saving={lat.parallel_arch_saving:.3f};"
                   f"model_speedup={lat.total_speedup:.1f}x")
        s = rec["search_us"]
        if "host_hash" in s:
            derived += (f";host_speedup_vs_serial_hash="
                        f"{s['host_hash'] / s['octent_kernel']:.1f}x")
        rows.append(csv_row(f"fig9a_search/{name}", s["octent_kernel"],
                            derived))
        for path in ("octent_ref", "xla_dense"):
            rows.append(csv_row(f"fig9a_search/{name}/{path}", s[path],
                                f"impl={kimpl}"))
        p = rec["plan_build_us"]
        rows.append(csv_row(
            f"fig9a_search/{name}/plan_build", p["counting"],
            f"argsort_us={p['argsort']:.1f};"
            f"sortfree_speedup={rec['plan_build_speedup']:.2f}x;"
            f"sort_ops={rec['sort_ops']['counting']}"))
    with open(OUT_JSON, "w") as f:
        json.dump(records, f, indent=2)
    return rows


def run_smoke(n: int = 96) -> list[str]:
    """Interpret-mode search-parity gate (tiny shapes, seconds): the
    octent kernel must match the host hash oracle bit for bit and the
    plan build must audit sort-free. Raises on any drift."""
    rng = np.random.default_rng(0)
    ext = 24
    lin = rng.choice(ext ** 3, size=n, replace=False)    # unique coords
    coords = np.stack([lin % ext, (lin // ext) % ext, lin // ext ** 2],
                      axis=-1).astype(np.int32)
    bidx = rng.integers(0, 2, n).astype(np.int32)
    valid = np.arange(n) < n - 8
    km_hash = mapsearch.build_kmap_hash(coords, bidx, valid,
                                        morton.subm3_offsets())
    c, b, v = jnp.asarray(coords), jnp.asarray(bidx), jnp.asarray(valid)
    rec, km = _search_case(c, b, v, max_blocks=n, kimpl="interpret", bm=8)
    if not (km == km_hash).all():
        raise AssertionError("octent kernel drifted from the hash oracle")
    s = rec["search_us"]
    return [csv_row("search_smoke/octent_kernel", s["octent_kernel"],
                    f"impl=interpret;parity=hash;voxels={n}"),
            csv_row("search_smoke/plan_build",
                    rec["plan_build_us"]["counting"],
                    f"sort_ops={rec['sort_ops']['counting']};"
                    f"query_tensor_ops={rec['query_tensor_ops']}")]


def sharded_smoke_child(n: int = 96) -> list[str]:
    """Body of the 8-device sharded gate (run via run_smoke_sharded —
    the device-count flag must be set before jax initializes): sharded
    vs single-device kmap parity on one small cloud over 2/8-way meshes,
    plus the full-table-never-on-one-device jaxpr audit."""
    from jax.sharding import Mesh
    from repro.core import binning
    from repro.kernels.octent import sharded
    from jax import set_mesh

    assert len(jax.devices()) >= 8, (
        "sharded smoke needs 8 host devices; run benchmarks/search_speedup "
        "--smoke (the parent sets XLA_FLAGS) instead of --sharded-smoke")
    rng = np.random.default_rng(0)
    ext = 24
    lin = rng.choice(ext ** 3, size=n, replace=False)
    coords = np.stack([lin % ext, (lin // ext) % ext, lin // ext ** 2],
                      axis=-1).astype(np.int32)
    bidx = rng.integers(0, 2, n).astype(np.int32)
    valid = np.arange(n) < n - 8
    c, b, v = jnp.asarray(coords), jnp.asarray(bidx), jnp.asarray(valid)
    km_ref, nb_ref = oct_ops.build_kmap(c, b, v, max_blocks=n, impl="ref")
    rows = []
    for shape, names, nd in [((2,), ("data",), 2), ((8,), ("data",), 8)]:
        mesh = Mesh(np.array(jax.devices()[:nd]).reshape(shape), names)
        with set_mesh(mesh):
            jfn = jax.jit(lambda c, b, v: oct_ops.build_kmap(
                c, b, v, max_blocks=n, impl="sharded"))
            km, nb = jfn(c, b, v)
            jax.block_until_ready(km)    # first call pays trace+compile
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(c, b, v)[0])
            us = (time.perf_counter() - t0) * 1e6
            # audit shapes come from the actually-built table, so the
            # check cannot desynchronize from the padding policy
            sqt = sharded.build_query_table_sharded(c, b, v, max_blocks=n)
            s = sqt.n_shards
            n_pad = sqt.tkey.shape[0]
            fn = lambda c, b, v: sharded.build_kmap_sharded(
                c, b, v, max_blocks=n)[0]
            full = binning.shard_body_avals_with_shape(fn, c, b, v,
                                                       shape=(n_pad,))
            loc = binning.shard_body_avals_with_shape(fn, c, b, v,
                                                      shape=(n_pad // s,))
        if not (np.asarray(km) == np.asarray(km_ref)).all():
            raise AssertionError(f"sharded kmap drift on mesh {shape}")
        if int(nb) != int(nb_ref):
            raise AssertionError(f"sharded n_blocks drift on mesh {shape}")
        if s > 1 and (full != 0 or loc == 0):
            raise AssertionError(
                f"sharded audit: full-table avals={full}, slice avals={loc}")
        rows.append(csv_row(f"sharded_smoke/{s}way", us,
                            f"parity=ref;voxels={n};full_table_avals={full}"))
    return rows


def run_smoke_sharded() -> list[str]:
    """8-host-CPU-device sharded smoke gate (XLA's device count is fixed
    at jax init, so the child body runs through the shared
    tests/proptest.run_script subprocess harness). Raises on parity drift
    or audit regression; returns the child's CSV rows."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tests.proptest import run_script
    out = run_script(
        "from benchmarks.search_speedup import sharded_smoke_child\n"
        "for row in sharded_smoke_child():\n"
        "    print(row)\n", timeout=600)
    return [ln for ln in out.splitlines() if ln.startswith("sharded_smoke")]


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="interpret-mode parity gate on tiny shapes")
    ap.add_argument("--sharded-smoke", action="store_true",
                    help="8-device sharded parity gate (child mode; use "
                         "--smoke from a 1-device shell — it spawns this)")
    args = ap.parse_args()
    if args.sharded_smoke:
        rows = sharded_smoke_child()
    elif args.smoke:
        rows = run_smoke() + run_smoke_sharded()
    else:
        rows = run(full=False)
    for row in rows:
        print(row)
