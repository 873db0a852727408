#!/usr/bin/env python3
"""Compile every cell's served forward and map search for a described
TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py [cell ...]

For each cell's configuration and padding bucket, builds the plans of one
base scene on the CPU, then lowers and compiles for one chip of a
described ``v5e:2x2`` topology: the engine's forward (every sparse conv
through the fused GEMM kernel, at the bucket's real rulebook sizes) and
the OCTENT query kernel at the bucket's size. A kernel that the chip's
compiler refuses fails here. Nothing runs: no result, no time.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("REPRO_SEARCH_IMPL", "ref")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import reference  # noqa: E402
import run  # noqa: E402
import scenes  # noqa: E402


def rehearse(spec: dict, one_chip) -> None:
    from repro.core.spconv import SparseTensor
    from repro.kernels.octent.kernel import octent_query
    from repro.launch.spconv_serve import merge_plans, split_plans
    from repro.models import minkunet

    cfg, traffic = spec["config"], spec["traffic"]
    arch = reference.arch(cfg)
    bucket = int(traffic["bucket"])
    prog = minkunet.MinkUNetConfig(
        name=cfg["name"], in_ch=arch.in_ch, classes=arch.classes,
        stem=arch.stem, enc=arch.enc, dec=arch.dec, blocks=arch.blocks)
    c, f = scenes.base_pool(dict(traffic, pool=1))[0]
    arrays = [jnp.asarray(x) for x in scenes.padded(c, f, bucket)]
    plans = minkunet.build_plans(*arrays[:3], prog, n_max=bucket)
    dyn, treedef, static, _ = split_plans(plans)

    def spec_of(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.eval_shape(lambda: reference._init(arch, jax.random.key(0)))

    @jax.jit
    def forward(params, coords, batch, valid, feats, dyn):
        return minkunet.forward(params, SparseTensor(coords, batch, valid,
                                                     feats), prog,
                                plans=merge_plans(treedef, static, dyn),
                                impl="pallas")

    lowered = forward.lower(
        jax.tree_util.tree_map(spec_of, params),
        *[spec_of(a) for a in (arrays[0], arrays[1], arrays[2], arrays[3])],
        [None if d is None else spec_of(d) for d in dyn])
    compiled = lowered.compile()
    kernels = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    m_pads = sorted({int(p.tiles.gather_idx.shape[0])
                     for p in (*plans.subm, *plans.down, *plans.up)})
    print(f"{spec['cell']['name']}: forward compiled, bucket {bucket}, "
          f"{c.shape[0]} voxels, M_pad {m_pads}, {kernels} kernel calls, "
          f"temp {getattr(mem, 'temp_size_in_bytes', None)} B, "
          f"arguments {getattr(mem, 'argument_size_in_bytes', None)} B")

    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                            sharding=one_chip)
    fn = jax.jit(lambda q, o, u, tk, tv, nb: octent_query(q, o, u, tk, tv,
                                                          nb))
    fn.lower(s(5, bucket), s(27, 3), s(bucket), s(bucket), s(bucket),
             s(1)).compile()
    print(f"{spec['cell']['name']}: octent_query compiled at N {bucket}")


def main(argv) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    names = argv or [w["name"] for w in bench["workloads"]]
    seen = set()
    for name in names:
        spec = run.cell_spec(run.ROOT, name)
        key = (spec["cell"]["config"], spec["traffic"]["bucket"])
        if key in seen:
            continue
        seen.add(key)
        rehearse(spec, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
