import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init) — brief: MULTI-POD DRY-RUN step 0.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each applicable cell the train/prefill/decode step is lowered with
ShapeDtypeStruct stand-ins (zero allocation), compiled for the 16x16
single-pod and 2x16x16 multi-pod host-device meshes, and the compiled
artifact is mined for:

  * memory_analysis()  — per-device bytes (proves it fits 16 GB HBM),
  * cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * post-SPMD HLO text — collective bytes by kind (hlo_analysis).

Results land in benchmarks/results/dryrun/*.json (append-only, resumable);
EXPERIMENTS.md §Dry-run/§Roofline and benchmarks/roofline.py read them.

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
from jax import set_mesh

from repro.configs import SHAPE_CELLS, cell_applicable, get_config, list_archs
from repro.launch import hlo_analysis, shardings
from repro.launch.mesh import make_production_mesh
from repro.launch.train import make_train_step
from repro.models import api
from repro.optim import adamw
from repro.runtime import flags
from repro.runtime import sharding as rsharding

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")


def build_cell(model: api.Model, cell, mesh, *, strategy: str = "tp",
               kv_layout: str = "kv"):
    """Returns (fn, abstract_args, in_shardings, out_shardings, donate)."""
    cfg = model.cfg
    params_abs = model.abstract_params()
    p_sh = shardings.param_shardings(params_abs, mesh, strategy)
    batch_abs = model.input_specs(cell)
    b_sh = shardings.batch_shardings(batch_abs, mesh)

    if cell.kind == "train":
        opt_abs = jax.eval_shape(adamw.init, params_abs)
        o_sh = shardings.opt_state_shardings(opt_abs, mesh, strategy)
        step = make_train_step(model, adamw.AdamWConfig())
        return (step, ((params_abs, opt_abs), batch_abs),
                ((p_sh, o_sh), b_sh), ((p_sh, o_sh), None), (0,))
    if cell.kind == "prefill":
        fn = lambda p, b: model.prefill(p, b, cell.seq_len)   # noqa: E731
        return fn, (params_abs, batch_abs), (p_sh, b_sh), None, ()
    # decode: one step against a seq_len-deep cache
    cache_abs = api.abstract_cache(model, cell)
    c_sh = shardings.cache_shardings(cache_abs, mesh, kv_layout)
    t_sh = shardings.batch_shardings(batch_abs, mesh)
    fn = model.decode_step
    return (fn, (params_abs, cache_abs, batch_abs["tokens"]),
            (p_sh, c_sh, t_sh["tokens"]), (None, c_sh), (1,))


def _depth_variants(cfg):
    """Two shallow same-width configs + the unit count for extrapolation.

    XLA cost analysis counts while-loop bodies once (runtime.flags), so true
    costs are measured on fully-unrolled depth-1/2 variants and scaled:
    total = F(d1) + (units - 1) * (F(d2) - F(d1)). Exact for homogeneous
    stacks (incl. rglru groups: both variants carry the same 2-layer tail).
    """
    if cfg.family == "rglru":
        tail = cfg.n_layers % 3
        return (dataclasses.replace(cfg, n_layers=3 + tail),
                dataclasses.replace(cfg, n_layers=6 + tail),
                cfg.n_layers // 3)
    return (dataclasses.replace(cfg, n_layers=1),
            dataclasses.replace(cfg, n_layers=2), cfg.n_layers)


def measure_costs(cfg, cell, mesh, *, strategy: str = "tp",
                  kv_layout: str = "kv", donate: bool = False) -> dict:
    """Loop-corrected FLOPs / bytes / collective bytes for the full depth."""
    c1, c2, units = _depth_variants(cfg)
    meas = {}
    for tag, c in (("d1", c1), ("d2", c2)):
        model = api.build_model(c)
        fn, args, in_sh, out_sh, dn = build_cell(
            model, cell, mesh, strategy=strategy, kv_layout=kv_layout)
        with flags.unroll_for_cost():
            with set_mesh(mesh):
                compiled = jax.jit(
                    fn, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=dn if donate else (),
                ).lower(*args).compile()
        cost = compiled.cost_analysis() or {}
        coll = hlo_analysis.parse_collectives(compiled.as_text())
        # cost_analysis runs on the SPMD-partitioned per-device module;
        # scale to whole-program totals (verified: per-device flops x chips
        # == 8*N*D for full-remat training, EXPERIMENTS.md §Methodology)
        n = mesh.size
        meas[tag] = {"flops": float(cost.get("flops", 0.0)) * n,
                     "bytes": float(cost.get("bytes accessed", 0.0)) * n,
                     "coll": float(coll.total_bytes) * n,
                     "coll_by_kind": {k: v * n
                                      for k, v in coll.bytes_by_kind.items()}}

    def extrap(key):
        per = max(meas["d2"][key] - meas["d1"][key], 0.0)
        return meas["d1"][key] + (units - 1) * per

    kinds = set(meas["d1"]["coll_by_kind"]) | set(meas["d2"]["coll_by_kind"])
    coll_by_kind = {}
    for k in kinds:
        a = meas["d1"]["coll_by_kind"].get(k, 0.0)
        b = meas["d2"]["coll_by_kind"].get(k, 0.0)
        coll_by_kind[k] = a + (units - 1) * max(b - a, 0.0)
    return {"flops": extrap("flops"), "bytes": extrap("bytes"),
            "collective_bytes": extrap("coll"),
            "collective_bytes_by_kind": coll_by_kind,
            "per_unit_flops": max(meas["d2"]["flops"] - meas["d1"]["flops"], 0.0),
            "depth_units": units}


def run_cell(arch: str, shape: str, mesh_kind: str,
             save_hlo: bool = False, *, strategy: str = "tp",
             kv_layout: str = "kv", donate: bool = False) -> dict:
    cfg = get_config(arch)
    cell = SHAPE_CELLS[shape]
    ok, why = cell_applicable(cfg, cell)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "kind": cell.kind, "status": "skip", "skip_reason": why,
           "strategy": strategy, "kv_layout": kv_layout, "donate": donate}
    if not ok:
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    model = api.build_model(cfg)
    if strategy == "pure_dp":
        rsharding.set_batch_axes(("pod", "data", "model"))
    try:
        fn, args, in_sh, out_sh, dn = build_cell(
            model, cell, mesh, strategy=strategy, kv_layout=kv_layout)

        with set_mesh(mesh):
            t0 = time.time()
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=dn if donate else ())
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0

        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            mem_rec = {k: int(getattr(mem, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)}
        except Exception:                                # noqa: BLE001
            mem_rec = {}
        hlo = compiled.as_text()
        coll_raw = hlo_analysis.parse_collectives(hlo)
        rec.update({
            "status": "ok", "n_chips": n_chips,
            "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "hlo_flops_raw_loop_body_once": float(cost.get("flops", 0.0)),
            "collective_count_by_kind_raw": coll_raw.count_by_kind,
            "memory_analysis": mem_rec,
        })

        # roofline terms from loop-corrected whole-program costs — single-pod
        # only (the multi-pod pass proves the 'pod' axis lowers/compiles)
        if mesh_kind == "single":
            corr = measure_costs(cfg, cell, mesh, strategy=strategy,
                                 kv_layout=kv_layout, donate=donate)
            flops, hbm_bytes = corr["flops"], corr["bytes"]
            terms = hlo_analysis.roofline_terms(
                flops, hbm_bytes, corr["collective_bytes"], n_chips)
            mf = hlo_analysis.model_flops(cfg, cell)
            rec.update({
                "hlo_flops": flops, "hlo_bytes": hbm_bytes,
                "collective_bytes": corr["collective_bytes"],
                "collective_bytes_by_kind": corr["collective_bytes_by_kind"],
                "depth_units": corr["depth_units"],
                "model_flops": mf,
                "useful_flops_ratio": (mf / flops) if flops else 0.0,
                **terms,
            })
        if save_hlo:
            hdir = os.path.join(RESULTS_DIR, "hlo")
            os.makedirs(hdir, exist_ok=True)
            with open(os.path.join(
                    hdir, f"{arch}__{shape}__{mesh_kind}.hlo"), "w") as f:
                f.write(hlo)
    finally:
        rsharding.set_batch_axes(("pod", "data"))
    return rec


def result_path(arch, shape, mesh_kind, tag=""):
    suffix = f"__{tag}" if tag else ""
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="perf-iteration tag")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--remat", default=None,
                    choices=[None, "full", "dots", "dots_no_batch"])
    ap.add_argument("--strategy", default="tp", choices=["tp", "pure_dp"])
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, "einsum", "shard_map"])
    ap.add_argument("--cache-shard", default="kv", choices=["kv", "ctx"])
    ap.add_argument("--donate", action="store_true")
    args = ap.parse_args()

    if args.remat:
        from repro.models import transformer
        transformer.set_remat_mode(args.remat)
    if args.moe_impl:
        from repro.models import moe
        moe.set_moe_impl(args.moe_impl)

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPE_CELLS) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(RESULTS_DIR, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = result_path(arch, shape, mesh_kind, args.tag)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {arch} {shape} {mesh_kind}")
                    continue
                print(f"[run] {arch} {shape} {mesh_kind} ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mesh_kind, args.save_hlo,
                                   strategy=args.strategy,
                                   kv_layout=args.cache_shard,
                                   donate=args.donate)
                except Exception as e:                   # noqa: BLE001
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "fail", "error": repr(e),
                           "traceback": traceback.format_exc()}
                rec["tag"] = args.tag
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skip"
                n_fail += st == "fail"
                extra = (f" compute={rec.get('compute_s', 0):.3e}s "
                         f"mem={rec.get('memory_s', 0):.3e}s "
                         f"coll={rec.get('collective_s', 0):.3e}s "
                         f"compile={rec.get('compile_s', '-')}s"
                         if st == "ok" else rec.get("skip_reason",
                                                    rec.get("error", "")))
                print(f"  -> {st}{extra}", flush=True)
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail}")


if __name__ == "__main__":
    main()
