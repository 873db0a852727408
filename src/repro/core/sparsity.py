"""SPAC: inherent-sparsity exploitation (paper §V-B), TPU-adapted.

The ASIC's Gather Unit strobes individual zero operands in front of a 16x16
MAC array. A 128x128 MXU cannot gate individual lanes, so the saving
mechanism is re-grained (DESIGN.md §2, §14):

  * row grain   — maps whose source voxel row is entirely zero are dropped
    from the kmap (:func:`compact_kmap`); the gather never issues them.
  * block grain — per-(row, Cin-block) liveness (:func:`row_block_nonzero`)
    lets the fused kernel skip the DMA and MAC of a dead Cin block inside
    an otherwise-live tile (kernels/spconv_gemm, DESIGN.md §14).
  * tile grain  — (bm x bk) input tiles that are entirely zero are skipped
    inside kernels/masked_matmul via a precomputed block mask
    (:func:`block_mask`).

Elision at every grain is **forward-only** lossless: a zero row contributes
exactly 0 to each partial sum, but its gradient w.r.t. the features is
wᵀ·g ≠ 0, so backward passes must differentiate the un-elided geometry
math (DESIGN.md §2 — the custom VJPs in kernels/spconv_gemm/ops.py and
core/rulebook.py implement the rule).

:class:`ActSparsity` threads the post-ReLU zero pattern from one layer's
fused epilogue into the next layer's masks without re-sweeping the feature
array in HBM. :func:`refresh_log` counts the liveness refreshes of a
traced forward that could not short-circuit. :func:`sparsity_stats`
quantifies the grains against the paper's element grain so the
granularity loss is measurable (EXPERIMENTS.md §Paper-fidelity).
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple

import jax.numpy as jnp

#: open refresh logs, innermost last (see :func:`refresh_log`)
_REFRESH_LOGS: list[list] = []


@contextlib.contextmanager
def refresh_log():
    """Collect the "full" flag of every SPAC liveness refresh made inside.

    ``ops.refresh_liveness`` appends one int32 scalar per refresh to the
    innermost open log: 1 where it ran the full per-slot gather, 0 where
    it short-circuited to the geometry flags. Open it around the tracing
    of a forward and return ``sum(log)`` next to the answer; ``len(log)``
    is then the static number of refreshes per call (the serve engine's
    ``spac.refresh`` / ``spac.refresh_full`` counters).
    """
    log: list = []
    _REFRESH_LOGS.append(log)
    try:
        yield log
    finally:
        _REFRESH_LOGS.pop()


def note_refresh(full) -> None:
    """Record one refresh's "full" flag in the innermost open log."""
    if _REFRESH_LOGS:
        _REFRESH_LOGS[-1].append(jnp.asarray(full, jnp.int32))


def row_nonzero(feats: jnp.ndarray) -> jnp.ndarray:
    """(N,) bool — row has any nonzero element (post-ReLU survivors)."""
    return jnp.any(feats != 0, axis=-1)


def row_block_nonzero(feats: jnp.ndarray, bk: int) -> jnp.ndarray:
    """(N, Cin/bk) bool — Cin block of the row has any nonzero element.

    The per-(row, block) face of SPAC (DESIGN.md §14): feeds
    ``ops.tile_block_liveness`` so the fused kernel skips dead Cin blocks
    inside live tiles. ``bk`` must divide the channel count.
    """
    n, c = feats.shape
    if c % bk != 0:
        raise ValueError(f"bk={bk} must divide the channel count {c}")
    return jnp.any(feats.reshape(n, c // bk, bk) != 0, axis=-1)


def compact_kmap(kmap: jnp.ndarray, row_nz: jnp.ndarray) -> jnp.ndarray:
    """Drop maps whose source row is all-zero: they contribute nothing.

    This is the TPU face of the Gather Unit — elision is recorded in the
    rulebook instead of gated in the datapath. Forward-only: differentiate
    through :func:`repro.core.rulebook.apply_kmap_gather_spac`, never
    through the compacted map directly (DESIGN.md §2).
    """
    src_nz = jnp.take(row_nz, jnp.maximum(kmap, 0), axis=0)
    return jnp.where((kmap >= 0) & src_nz, kmap, -1)


def block_mask(x: jnp.ndarray, bm: int, bk: int) -> jnp.ndarray:
    """(M/bm, K/bk) bool — tile has any nonzero element. Feeds the
    @pl.when skip in kernels/masked_matmul. Raises ``ValueError`` on
    non-multiple shapes (a bare assert would vanish under ``python -O``);
    ``masked_matmul.ops.sparse_dense_matmul`` pads-and-slices for you."""
    m, k = x.shape
    if m % bm != 0 or k % bk != 0:
        raise ValueError(
            f"block_mask needs tile-multiple shapes, got ({m}, {k}) for "
            f"bm={bm}, bk={bk}; pad before masking")
    t = x.reshape(m // bm, bm, k // bk, bk)
    return jnp.any(t != 0, axis=(1, 3))


class ActSparsity(NamedTuple):
    """Activation-sparsity masks threaded layer-to-layer (DESIGN.md §14).

    Emitted by the fused BN/ReLU epilogue *in-kernel* (the output block is
    VMEM-resident when the ReLU lands, so the zero pattern is free) and
    consumed by the next layer's SPAC liveness refresh — no per-layer
    ``row_nonzero`` re-sweep of the feature array in HBM.

    ``blk_nz`` covers column groups of width ``blk``; groups may overhang
    the true channel count (the overhang columns are zero-padded lanes,
    never live). ``blk_nz is None`` means row grain only.
    """

    row_nz: jnp.ndarray                 # (N,) bool
    blk_nz: jnp.ndarray | None = None   # (N, G) bool, G*blk >= C
    blk: int = 0                        # column-group width (0: row only)

    def block_liveness(self, c_in: int, bk: int) -> jnp.ndarray | None:
        """(N, c_in/bk) bool when the threaded groups align with the
        consumer's Cin blocking (bk a multiple of ``blk``), else None —
        the consumer then falls back to a fresh sweep or row grain."""
        if self.blk_nz is None or self.blk <= 0:
            return None
        if bk % self.blk != 0 or c_in % bk != 0:
            return None
        gpb = bk // self.blk
        n_k = c_in // bk
        if n_k * gpb > self.blk_nz.shape[1]:
            return None
        n = self.blk_nz.shape[0]
        return self.blk_nz[:, :n_k * gpb].reshape(n, n_k, gpb).any(-1)


def act_from_feats(feats: jnp.ndarray, blk: int = 128) -> ActSparsity:
    """Sweep the feature array once into an :class:`ActSparsity` (the
    fallback when no epilogue-emitted act is threaded)."""
    n, c = feats.shape
    g = -(-c // blk)
    pad = g * blk - c
    f = jnp.pad(feats, ((0, 0), (0, pad))) if pad else feats
    blk_nz = jnp.any(f.reshape(n, g, blk) != 0, axis=-1)
    return ActSparsity(row_nz=blk_nz.any(-1), blk_nz=blk_nz, blk=blk)


class SparsityStats(NamedTuple):
    element_sparsity: jnp.ndarray   # fraction of zero elements (paper grain)
    row_sparsity: jnp.ndarray       # fraction of all-zero rows
    map_elision: jnp.ndarray        # fraction of valid maps dropped row-wise
    macs_dense: jnp.ndarray         # MACs without sparsity
    macs_row_elided: jnp.ndarray    # MACs after row-grain elision


def sparsity_stats(feats: jnp.ndarray, kmap: jnp.ndarray,
                   c_out: int) -> SparsityStats:
    valid = kmap >= 0
    nz_rows = row_nonzero(feats)
    src_nz = jnp.take(nz_rows, jnp.maximum(kmap, 0), axis=0)
    kept = valid & src_nz
    c_in = feats.shape[-1]
    dense = valid.sum() * c_in * c_out
    elided = kept.sum() * c_in * c_out
    n_valid = valid.sum()
    # an empty kmap elides nothing: 0.0, not the clamp artifact 1 - 0/1
    elision = jnp.where(n_valid > 0,
                        1.0 - kept.sum() / jnp.maximum(n_valid, 1), 0.0)
    return SparsityStats(
        element_sparsity=(feats == 0).mean(),
        row_sparsity=1.0 - nz_rows.mean(),
        map_elision=elision,
        macs_dense=dense,
        macs_row_elided=elided,
    )
