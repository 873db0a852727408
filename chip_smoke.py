#!/usr/bin/env python3
"""Chip smoke test: MinkUNet-small served end to end on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # four chips: the mesh-sharded search

One chip: a ``ServeEngine`` at the published MinkUNet-small widths
(``models/minkunet.SMALL``, random weights from ``--seed``) serves one
warm-up and ``--requests`` Seg(i)-sized indoor clouds (16,384 voxels, the
top padding bucket) with ``impl="pallas"``: map search runs the OCTENT
query kernel and every sparse conv the fused GEMM kernel. One request's
kmaps are compared bit for bit with the ``ref`` search, and its logits
with the float32 ``ref`` forward on the same chip.

Four chips: MinkUNet-small's plans for one Seg(i) cloud are built under a
4-device mesh, where the search resolves to the sharded engine; the
kmaps must equal the single-device build bit for bit, and each device
must hold only its slice of the search table.

One process, which never starts a child that touches JAX. Without a TPU
it exits non-zero and prints no result. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed on the way are a smoke, not a measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Seg(i) cloud size (benchmarks/common.py), the top serve bucket
SEG_I_VOXELS = 16384


class SmokeFailure(Exception):
    """A phase of the smoke failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def seg_i_cloud(seed: int, n_voxels: int):
    """One seeded indoor scene voxelized into an ``n_voxels`` batch."""
    from repro.data import pointcloud
    return pointcloud.make_batch(np.random.default_rng(seed), "indoor",
                                 batch_size=1, max_voxels=n_voxels)


def f32_tolerance(cfg) -> float:
    """Bound on max|served - ref| / max|ref| (derivation in CHANGES.md).

    Every matmul on both paths contracts float32 at HIGHEST precision (a
    multi-pass bf16 product on the MXU). Per layer this leaves a relative
    error of at most 2**-16 (the bf16x3 split, the coarser of the
    multi-pass modes), widened 4x for the tails of the max over all
    logits; the layers' errors add up linearly. One layer run as a single
    bf16 pass alone gives about 2**-9, well above the bound.
    """
    layers = 2 + (len(cfg.enc) + len(cfg.dec)) * (1 + cfg.blocks)
    return layers * 2.0 ** -14


def serve_phase(cfg, *, n_voxels: int, n_requests: int, seed: int,
                impl: str, search_impl: str) -> None:
    """Serve a warm-up plus ``n_requests`` clouds; check health, the
    kmaps and the logits against the f32 oracle."""
    import jax
    import jax.numpy as jnp
    from repro.core.spconv import SparseTensor
    from repro.kernels.octent import ops as oct_ops
    from repro.launch.spconv_serve import ServeEngine, merge_plans, split_plans
    from repro.models import minkunet
    from repro.runtime import admission, guard

    check(admission.bucket_for(n_voxels) == n_voxels,
          f"{n_voxels} voxels is not a padding bucket")
    resolved = oct_ops.search_impl()
    print(f"resolved impls: search={resolved} gemm={impl}")
    check(resolved == search_impl,
          f"map search resolved to {resolved!r}, not {search_impl!r}")

    params = minkunet.init_model(cfg, jax.random.key(seed))
    engine = ServeEngine(params, cfg, impl=impl, max_batch=n_requests)
    clouds = [seg_i_cloud(seed + 1 + i, n_voxels)
              for i in range(n_requests + 1)]

    def serve(batch):
        t0 = time.perf_counter()
        for i, vb in batch:
            engine.submit(f"req-{i}", vb.coords, vb.batch, vb.valid,
                          vb.feats, deadline_s=1200.0)
        engine.drain()
        return time.perf_counter() - t0

    setup_s = serve([(0, clouds[0])])
    print(f"set-up (warm-up request, compiles included): {setup_s:.3f} s; "
          f"executables compiled: {engine.compiled}")
    wall_s = serve(list(enumerate(clouds))[1:])
    done = [r for r in engine.results if r.status == "completed"]
    lat = [r.latency_s for r in done[1:]]
    print(f"served {len(done) - 1} warm requests in {wall_s:.3f} s "
          f"(smoke, not a metric; per-request latency "
          f"{', '.join(f'{x:.3f}' for x in lat)} s)")
    stats = engine.stats()
    check(stats["completed"] == n_requests + 1
          and stats["requests"] == n_requests + 1,
          f"not every request completed: {stats}")
    check(stats["level"] == 0, f"degradation ladder left level 0: {stats}")
    bad = {k: v for k, v in guard.health().snapshot().items()
           if v and k.startswith(("fallback.", "quarantine.",
                                  "serve.degrade.", "serve.shed",
                                  "serve.isolated"))}
    check(not bad, f"health counters show a fallback or degradation: {bad}")
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    print(f"device peak bytes in use: {mem.get('peak_bytes_in_use')}")

    # -- one request against the oracles: bit-identical kmaps from the
    # ref search, and the f32 ref forward over the ref-built plans
    vb = clouds[1]
    served = next(r for r in engine.results if r.rid == "req-1").logits
    c, b, v, f = map(jnp.asarray, (vb.coords, vb.batch, vb.valid, vb.feats))
    plans = minkunet.build_plans(c, b, v, cfg, n_max=n_voxels)
    os.environ["REPRO_SEARCH_IMPL"] = "ref"
    try:
        plans_ref = minkunet.build_plans(c, b, v, cfg, n_max=n_voxels)
    finally:
        del os.environ["REPRO_SEARCH_IMPL"]
    got = jax.tree_util.tree_leaves(plans)
    want = jax.tree_util.tree_leaves(plans_ref)
    check(len(got) == len(want) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(got, want)),
        f"{search_impl} plans differ from the ref search")
    print(f"plans: {len(got)} arrays bit-identical to the ref search")

    dyn, treedef, static, _ = split_plans(plans_ref)

    @jax.jit
    def ref_forward(params, c, b, v, f, dyn):
        return minkunet.forward(params, SparseTensor(c, b, v, f), cfg,
                                plans=merge_plans(treedef, static, dyn),
                                impl="ref")

    with jax.default_matmul_precision("float32"):
        ref = np.asarray(ref_forward(params, c, b, v, f, dyn))
    served = np.asarray(served)
    check(served.shape == ref.shape == (n_voxels, cfg.classes),
          f"logits shape {served.shape} vs ref {ref.shape}")
    check(bool(np.isfinite(served).all()), "served logits are not finite")
    err = float(np.abs(served - ref).max())
    scale = float(np.abs(ref).max())
    rel = err / scale if scale else float("inf")
    tol = f32_tolerance(cfg)
    print(f"served vs f32 ref: max abs err {err:.6g}, max|ref| {scale:.6g}, "
          f"max rel err {rel:.6g} (tolerance {tol:.6g})")
    check(rel <= tol, f"served logits off the f32 ref: {rel:.6g} > {tol:.6g}")


def four_chip_phase(cfg, *, n_voxels: int, seed: int) -> None:
    """MinkUNet-small plans under a 4-device mesh vs one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import binning, plan as planlib
    from repro.kernels.octent import ops as oct_ops, sharded
    from repro.kernels.octent.kernel import LANE
    from repro.models import minkunet

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chip needs 4 devices, found {len(devs)}")
    vb = seg_i_cloud(seed + 1, n_voxels)
    c, b, v = map(jnp.asarray, (vb.coords, vb.batch, vb.valid))

    single_impl = oct_ops.search_impl()
    single = minkunet.build_plans(c, b, v, cfg, n_max=n_voxels,
                                  cache=planlib.PlanCache())
    mesh = Mesh(np.array(devs[:4]), ("data",))
    with jax.set_mesh(mesh):
        impl = oct_ops.search_impl()
        print(f"resolved search impl: one device {single_impl}, "
              f"mesh {impl}")
        check(impl == "sharded", f"mesh search resolved to {impl!r}")
        t0 = time.perf_counter()
        meshed = minkunet.build_plans(c, b, v, cfg, n_max=n_voxels,
                                      cache=planlib.PlanCache())
        jax.block_until_ready(meshed)
        print(f"mesh plan build (compiles included): "
              f"{time.perf_counter() - t0:.3f} s")
        sqt = sharded.build_query_table_sharded(c, b, v,
                                                max_blocks=n_voxels)
        # the shape audit counts (n_pad,) values in the shard bodies, and
        # the query stream of a full bucket is (n_pad,) too: audit the
        # same cloud with LANE more invalid rows, so the lengths differ
        pad = lambda x: jnp.pad(x, [(0, LANE)] + [(0, 0)] * (x.ndim - 1))
        ca, ba, va = pad(c), pad(b), pad(v)
        audit = sharded.build_query_table_sharded(ca, ba, va,
                                                  max_blocks=n_voxels)
        s, n_pad = audit.n_shards, audit.tkey.shape[0]
        fn = lambda c, b, v: sharded.build_kmap_sharded(
            c, b, v, max_blocks=n_voxels)[0]
        full = binning.shard_body_avals_with_shape(fn, ca, ba, va,
                                                   shape=(n_pad,))
        local = binning.shard_body_avals_with_shape(fn, ca, ba, va,
                                                    shape=(n_pad // s,))
    check(s == 4, f"table split {s} ways, not 4")
    check(full == 0 and local > 0,
          f"shard bodies hold full-table values ({full}) or no slices "
          f"({local})")
    for name in ("ublocks", "tkey", "tval"):
        arr = getattr(sqt, name)
        held = {sh.device: sh.data.shape for sh in arr.addressable_shards}
        print(f"{name}: {arr.shape} -> per device "
              f"{sorted((d.id, shp) for d, shp in held.items())}")
        check(set(held) == set(devs[:4]),
              f"{name} sits on devices {sorted(d.id for d in held)}")
        check(all(shp == (arr.shape[0] // 4,) for shp in held.values()),
              f"{name} shards are not quarter slices: {held}")
    got = jax.tree_util.tree_leaves(meshed)
    want = jax.tree_util.tree_leaves(single)
    check(len(got) == len(want) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(got, want)),
        "sharded plans differ from the single-device build")
    print(f"sharded plans: {len(got)} arrays bit-identical to the "
          f"single-device build")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-device sharded-search phase")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    print(f"devices: {devs}")
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models import minkunet
    print(f"compile cache: {setup_compile_cache()}")

    t0 = time.perf_counter()
    try:
        if args.four_chip:
            four_chip_phase(minkunet.SMALL, n_voxels=SEG_I_VOXELS,
                            seed=args.seed)
        else:
            serve_phase(minkunet.SMALL, n_voxels=SEG_I_VOXELS,
                        n_requests=args.requests, seed=args.seed,
                        impl="pallas", search_impl="pallas")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
