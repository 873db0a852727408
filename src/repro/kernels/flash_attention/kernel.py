"""Pallas TPU kernel: blocked flash attention (causal / SWA / GQA).

The LM-side compute hot spot of the assigned architectures: online-softmax
attention with (bq x d) @ (d x bkv) MXU tiles, running max/denominator in
VMEM scratch carried across the innermost kv grid dimension, and structural
block skipping for causal + sliding-window patterns (out-of-window kv blocks
are never loaded — the same "don't issue zero work" principle as SPAC).

Grid: (B, Hq, Sq/bq, Skv/bkv), kv innermost (arbitrary).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bkv: int, n_kv: int, sq: int, skv: int,
            causal: bool, window: int, scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # structural skip: whole kv block outside the causal/window band
    q_lo = qi * bq + (skv - sq)               # absolute pos of first q row
    q_hi = q_lo + bq - 1
    k_lo = kj * bkv
    k_hi = k_lo + bkv - 1
    live = True
    if causal:
        live &= k_lo <= q_hi
    if window > 0:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                       # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        msk = k_pos < skv
        if causal:
            msk &= k_pos <= q_pos
        if window > 0:
            msk &= k_pos > q_pos - window
        s = jnp.where(msk, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, bq: int = 128,
                    bkv: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q (B, Hq, Sq, D); k, v (B, Hkv, Skv, D). See ref.py for semantics."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    n_q, n_kv = sq // bq, skv // bkv

    grid = (b, hq, n_q, n_kv)
    kern = functools.partial(
        _kernel, bq=bq, bkv=bkv, n_kv=n_kv, sq=sq, skv=skv,
        causal=causal, window=window, scale=d ** -0.5)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
