"""MinkUNet: the hooks through which the harness serves, checks and counts
a configuration whose ``family`` is ``"minkunet"``.

The architecture, weights and plain float32 reference are the
benchmark's own (``reference.py``, ``geometry.py``, ``counts.py``); the
served path is the program's ``ServeEngine`` over
``repro.models.minkunet``.
"""
from __future__ import annotations

import numpy as np

import counts
import geometry
import reference as unet

#: the kernels whose device time ``trace_reduce.summarize`` sums by name
kernels = ("octent_query", "spconv_gemm_fused")


def arch(cfg: dict) -> unet.Arch:
    return unet.arch(cfg)


def init_params(a: unet.Arch, seed: int) -> dict:
    return unet.init_params(a, seed)


def _program_config(a: unet.Arch, name: str):
    from repro.models import minkunet
    return minkunet.MinkUNetConfig(
        name=name, in_ch=a.in_ch, classes=a.classes, stem=a.stem,
        enc=a.enc, dec=a.dec, blocks=a.blocks)


def serve(a: unet.Arch, cfg: dict, params, *, bucket: int, clients: int,
          impl: str) -> dict:
    """The engine of one cell (one padding bucket, ``clients`` requests
    per tick) and the program entry points the harness times and marks:
    ``plan_build`` (the module attribute the engine calls per request)
    and ``dispatch`` (the engine's forward launch)."""
    from repro.launch.spconv_serve import ServeEngine
    from repro.models import minkunet
    from repro.runtime import admission

    prog = _program_config(a, cfg["name"])
    queue = admission.AdmissionQueue(buckets=(bucket,),
                                     grid_bits=prog.grid_bits,
                                     batch_bits=prog.batch_bits)
    engine = ServeEngine(params, prog, impl=impl, queue=queue,
                         max_batch=clients)
    return {"engine": engine, "plan_build": (minkunet, "build_plans"),
            "dispatch": (engine, "_forward_fn")}


def answer(result) -> np.ndarray:
    """Per-voxel logits of one completed request, ``bucket`` rows, the
    cloud's voxels first."""
    return result.logits


def reference(a: unet.Arch, params, coords: np.ndarray, feats: np.ndarray,
              bucket: int, *, precision: str = "highest") -> np.ndarray:
    """Reference logits ``(N, classes)`` of one cloud's ``N`` voxels."""
    hier = geometry.hierarchy(coords, len(a.enc))
    return unet.forward(a, params, feats, hier, bucket, precision=precision)


def cloud_work(a: unet.Arch, coords: np.ndarray, peaks: dict) -> dict:
    return counts.cloud_work(a, coords, peaks)


def control(a: unet.Arch):
    """An ``on_engine`` hook that puts the reference, at the next
    precision down, in the program's place: every request of the run is
    answered by it."""
    def hook(engine):
        def forward_fn(params, st, plans):
            import jax.numpy as jnp
            valid = np.asarray(st.valid)
            n = int(valid.sum())
            out = reference(a, params, np.asarray(st.coords)[:n],
                            np.asarray(st.feats)[:n], valid.shape[0],
                            precision="bf16x3")
            full = np.zeros((valid.shape[0], out.shape[1]), np.float32)
            full[:n] = out
            return jnp.asarray(full)
        engine._forward_fn = forward_fn
    return hook


def rehearse(a: unet.Arch, cfg: dict, coords: np.ndarray, feats: np.ndarray,
             bucket: int, sharding) -> str:
    """Lower and compile the served forward for the device of
    ``sharding`` (every sparse conv through the fused GEMM kernel, at the
    rulebook sizes of this cloud's plans, built here); nothing runs.
    Returns one line on what was compiled."""
    import jax
    import jax.numpy as jnp
    import scenes
    from repro.core.spconv import SparseTensor
    from repro.launch.spconv_serve import merge_plans, split_plans
    from repro.models import minkunet

    prog = _program_config(a, cfg["name"])
    arrays = [jnp.asarray(x) for x in scenes.padded(coords, feats, bucket)]
    plans = minkunet.build_plans(*arrays[:3], prog, n_max=bucket)
    dyn, treedef, static, _ = split_plans(plans)

    def spec_of(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params = jax.eval_shape(lambda: unet._init(a, jax.random.key(0)))

    @jax.jit
    def forward(params, coords, batch, valid, feats, dyn):
        return minkunet.forward(params, SparseTensor(coords, batch, valid,
                                                     feats), prog,
                                plans=merge_plans(treedef, static, dyn),
                                impl="pallas")

    compiled = forward.lower(
        jax.tree_util.tree_map(spec_of, params),
        *[spec_of(x) for x in arrays],
        [None if d is None else spec_of(d) for d in dyn]).compile()
    mem = compiled.memory_analysis()
    m_pads = sorted({int(p.tiles.gather_idx.shape[0])
                     for p in (*plans.subm, *plans.down, *plans.up)})
    return (f"forward compiled, bucket {bucket}, {coords.shape[0]} voxels, "
            f"M_pad {m_pads}, "
            f"{compiled.as_text().count('tpu_custom_call')} kernel calls, "
            f"temp {getattr(mem, 'temp_size_in_bytes', None)} B, "
            f"arguments {getattr(mem, 'argument_size_in_bytes', None)} B")
