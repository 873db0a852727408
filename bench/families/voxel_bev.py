"""Voxel-to-BEV detectors (OpenPCDet ``VoxelBackBone8x`` ->
``HeightCompression`` -> ``BaseBEVBackbone`` -> ``AnchorHeadSingle``, as
SECOND runs them): the hooks through which the harness serves, checks
and counts a configuration whose ``family`` is ``"voxel_bev"``.

The architecture, weights and plain float32 reference are the
benchmark's own (``voxel_bev_reference.py``, ``voxel_bev_counts.py``);
the served path is the program's ``ServeEngine`` over
``repro.models.second``. The answer of a request is the head maps, one
row per BEV cell.
"""
from __future__ import annotations

import numpy as np

import voxel_bev_counts as counts
import voxel_bev_reference as bev

#: the kernels whose device time ``trace_reduce.summarize`` sums by name
kernels = ("octent_query", "spconv_gemm_fused")


def arch(cfg: dict) -> bev.Arch:
    return bev.arch(cfg)


def init_params(a: bev.Arch, seed: int) -> dict:
    return bev.init_params(a, seed)


def _program_config(a: bev.Arch, name: str):
    from repro.models import second
    return second.SECONDConfig(
        name=name, in_ch=a.in_ch, sparse_shape=a.sparse_shape, stem=a.stem,
        stages=a.stages, bev_layers=a.bev_layers, bev_strides=a.bev_strides,
        bev_filters=a.bev_filters, up_strides=a.up_strides,
        up_filters=a.up_filters, anchors=a.anchors, classes=a.classes,
        box_code=a.box_code, dir_bins=a.dir_bins, bn_eps=a.bn_eps)


def serve(a: bev.Arch, cfg: dict, params, *, bucket: int, clients: int,
          impl: str) -> dict:
    """The engine of one cell (one padding bucket, ``clients`` requests
    per tick), and the program entry points the harness times and marks:
    ``plan_build`` (the module attribute the engine calls per request)
    and ``dispatch`` (the engine's forward launch)."""
    from repro.launch.spconv_serve import ServeEngine
    from repro.models import second
    from repro.runtime import admission

    prog = _program_config(a, cfg["name"])
    queue = admission.AdmissionQueue(buckets=(bucket,),
                                     grid_bits=prog.grid_bits,
                                     batch_bits=prog.batch_bits)
    engine = ServeEngine(params, prog, impl=impl, queue=queue,
                         max_batch=clients)
    return {"engine": engine, "plan_build": (second, "build_plans"),
            "dispatch": (engine, "_forward_fn")}


def answer(result) -> np.ndarray:
    """The head maps of one completed request, ``(H * W, cls + box +
    dir)``."""
    return result.logits


def reference(a: bev.Arch, params, coords: np.ndarray, feats: np.ndarray,
              bucket: int, *, precision: str = "highest") -> np.ndarray:
    """Reference head maps of one cloud."""
    return bev.forward(a, params, feats, bev.levels(a, coords), bucket,
                       precision=precision)


def cloud_work(a: bev.Arch, coords: np.ndarray, peaks: dict) -> dict:
    return counts.cloud_work(a, coords, peaks)


def control(a: bev.Arch):
    """An ``on_engine`` hook that puts the reference, at the next
    precision down, in the program's place: every request of the run is
    answered by it."""
    def hook(engine):
        def forward_fn(params, st, plans):
            import jax.numpy as jnp
            valid = np.asarray(st.valid)
            n = int(valid.sum())
            return jnp.asarray(reference(
                a, params, np.asarray(st.coords)[:n],
                np.asarray(st.feats)[:n], valid.shape[0],
                precision="bf16x3"))
        engine._forward_fn = forward_fn
    return hook


def rehearse(a: bev.Arch, cfg: dict, coords: np.ndarray, feats: np.ndarray,
             bucket: int, sharding) -> str:
    """Lower and compile the served forward's two executables for the
    device of ``sharding`` (every sparse conv through the fused GEMM
    kernel, at the rulebook sizes of this cloud's plans, built here);
    nothing runs. Returns one line on what was compiled."""
    import jax
    import jax.numpy as jnp
    import scenes
    from repro.core.spconv import SparseTensor
    from repro.launch.spconv_serve import merge_plans, split_plans
    from repro.models import second

    prog = _program_config(a, cfg["name"])
    arrays = [jnp.asarray(x) for x in scenes.padded(coords, feats, bucket)]
    plans = second.build_plans(*arrays[:3], prog, n_max=bucket)
    dyn, treedef, static, _ = split_plans(plans)

    def spec_of(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        spec_of, jax.eval_shape(lambda: bev._init(a, jax.random.key(0))))

    @jax.jit
    def sparse(params, coords, batch, valid, feats, dyn):
        return second.backbone(params, SparseTensor(coords, batch, valid,
                                                    feats), prog,
                               plans=merge_plans(treedef, static, dyn),
                               impl="pallas")

    one = sparse.lower(params, *[spec_of(x) for x in arrays],
                       [None if d is None else spec_of(d) for d in dyn])
    out = jax.tree_util.tree_map(spec_of, one.out_info)
    dense = jax.jit(lambda p, st: second.bev_head(p, st, prog)).lower(
        params, out).compile()
    one = one.compile()
    mems = [c.memory_analysis() for c in (one, dense)]
    return (f"backbone and BEV head compiled, bucket {bucket}, "
            f"{coords.shape[0]} voxels, "
            f"{one.as_text().count('tpu_custom_call')} kernel calls, "
            f"temp {[getattr(m, 'temp_size_in_bytes', None) for m in mems]} "
            f"B, arguments "
            f"{[getattr(m, 'argument_size_in_bytes', None) for m in mems]} B")
