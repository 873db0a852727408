"""Pallas TPU kernel: block-masked matmul (SPAC tile skipping, §V-B).

C = A @ B where (bm x bk) tiles of A known to be all-zero are never loaded
into the MXU: the block mask is scalar-prefetched and gates both the DMA
(via @pl.when) and the FLOPs. This is the single-GEMM face of the paper's
sparsity-aware computing — at the 40-60 % post-ReLU sparsity of Fig. 3(b),
clustered zeros skip whole tiles.

Grid: (m, n, k) with k innermost (arbitrary); accumulation lives in a VMEM
scratch accumulator, flushed to the output on the last k step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(mask_ref, a_ref, b_ref, out_ref, acc_ref, *, n_k: int):
    mi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[mi * n_k + ki] != 0)
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def masked_matmul(a: jnp.ndarray, b: jnp.ndarray, mask: jnp.ndarray,
                  *, bm: int = 128, bn: int = 128, bk: int = 128,
                  interpret: bool = False) -> jnp.ndarray:
    """a (M, K), b (K, N), mask (M//bm, K//bk) int32 (0 = skip tile)."""
    m, kdim = a.shape
    _, n = b.shape
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0
    n_m, n_n, n_k = m // bm, n // bn, kdim // bk
    assert mask.shape == (n_m, n_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, msk: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, msk: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, msk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="masked_matmul",
    )(mask.reshape(-1).astype(jnp.int32), a, b)
