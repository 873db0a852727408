"""The two Pallas kernels compile for a TPU v5e at Seg(i) size, and the
tile build at the benchmark cells' buckets.

Compiles ahead of time for a described (not attached) ``v5e:2x2`` chip,
so a kernel that Mosaic would refuse fails here, on a CPU host, before
any chip time is spent. Shapes are those of a Seg(i) cloud (16,384
voxels, 27 taps) and of its largest rulebook (450,560 map slots). Nothing
runs: these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.octent.kernel import octent_query
from repro.kernels.spconv_gemm import ops as sg_ops
from repro.kernels.spconv_gemm.kernel import spconv_gemm_fused

N = 16_384          # Seg(i) voxels (benchmarks/common.py)
K = 27              # Subm3 taps
M_PAD = 450_560     # rulebook slots of a Seg(i) layer
BM, BN, BO = 128, 128, 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_octent_query_compiles_for_v5e(one_chip):
    s = lambda *shape: _spec(one_chip, shape)
    fn = jax.jit(lambda q, o, u, tk, tv, nb: octent_query(q, o, u, tk, tv,
                                                          nb))
    compiled = fn.lower(s(5, N), s(K, 3), s(N), s(N), s(N), s(1)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("epilogue", [False, True],
                         ids=["plain", "epilogue"])
@pytest.mark.parametrize("cin,cout,bk", [(32, 32, None), (256, 256, 128)],
                         ids=["32to32", "256to256_bk128"])
def test_fused_gemm_compiles_for_v5e(one_chip, cin, cout, bk, epilogue):
    s = lambda *shape, dtype=jnp.int32: _spec(one_chip, shape, dtype)
    n_m = M_PAD // BM
    n_k = cin // (bk or cin)
    c_pad = -(-cout // BN) * BN     # ops.apply_tiles pads Cout to bn
    args = [s(N, cin, dtype=jnp.float32),
            s(K, cin, c_pad, dtype=jnp.float32),
            s(M_PAD), s(M_PAD)] + [s(n_m)] * 7 + [s(n_m, n_k), s(n_m)]
    if epilogue:
        args += [s(c_pad, dtype=jnp.float32), s(c_pad, dtype=jnp.float32),
                 s(N)]

    def f(*a):
        kw = dict(bm=BM, bn=BN, bo=BO, bk=bk, n_out_pad=N,
                  epilogue=epilogue)
        if epilogue:
            return spconv_gemm_fused(*a[:13], epi_scale=a[13],
                                     epi_shift=a[14], epi_valid=a[15], **kw)
        return spconv_gemm_fused(*a, **kw)

    compiled = jax.jit(f).lower(*args).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("n,k", [(40_960, 27), (40_960, 8), (69_632, 27),
                                 (69_632, 8)])
def test_tile_build_compiles_for_v5e(one_chip, n, k):
    """The default tile build at the ScanNet (40,960) and SemanticKITTI
    (69,632) buckets, Subm3 (27 taps) and strided (8 taps). Its
    compare-and-select over (tiles, bm, bo) must fuse into its reductions:
    materialised it would be 2.8-4.8 GB."""
    build = jax.jit(lambda km: sg_ops._build_tap_tiles(
        km, None, bm=BM, bo=BO, schedule=True, binning="counting"))
    compiled = build.lower(_spec(one_chip, (n, k))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 27
