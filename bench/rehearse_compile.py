#!/usr/bin/env python3
"""Compile every cell's served forward and map search for a described
TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py [cell ...]

For each cell's configuration and padding bucket, hands one base scene to
its family's ``rehearse`` hook, which builds the plans on the CPU and
lowers and compiles the served forward for one chip of a described
``v5e:2x2`` topology (every sparse conv through the fused GEMM kernel, at
the bucket's real rulebook sizes); then compiles the OCTENT query kernel
at the bucket's size, where the family names it. A kernel that the chip's
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

import run  # noqa: E402
import scenes  # noqa: E402


def rehearse(spec: dict, one_chip) -> None:
    from repro.kernels.octent.kernel import octent_query

    cfg, traffic, fam = spec["config"], spec["traffic"], spec["family"]
    name = spec["cell"]["name"]
    bucket = int(traffic["bucket"])
    c, f = scenes.base_pool(dict(traffic, pool=1))[0]
    print(f"{name}: " + fam.rehearse(fam.arch(cfg), cfg, c, f, bucket,
                                     one_chip))
    if "octent_query" not in fam.kernels:
        return
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                            sharding=one_chip)
    fn = jax.jit(lambda q, o, u, tk, tv, nb: octent_query(q, o, u, tk, tv,
                                                          nb))
    fn.lower(s(5, bucket), s(27, 3), s(bucket), s(bucket), s(bucket),
             s(1)).compile()
    print(f"{name}: octent_query compiled at N {bucket}")


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
