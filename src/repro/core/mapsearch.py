"""Map search: building SpConv IN-OUT maps.

This is the paper's first contribution (OCTENT, §IV). Several interchangeable
implementations are provided so the paper's own baselines exist in-tree:

  * :func:`build_kmap_bruteforce`  — the O(n^2) traverse of Fig. 3(a); oracle.
  * :func:`build_kmap_hash`        — host-side dict probing, the GPU-style
    hash baseline of [9]; oracle + Fig. 9(a) baseline.
  * :func:`build_kmap_octree`      — OCTENT: blockwise octree tables with the
    8-bank (= 8-lane) parallel query of Fig. 5(c). Fully jittable. Since
    PR 3 this dense-table XLA form is the ``search_impl='xla'`` oracle of
    the fused Pallas engine in kernels/octent (DESIGN.md §3), which is the
    default subm3 backend via plan.subm3_plan.
  * :func:`build_kmap_sorted`      — beyond-paper variant: no tables at all,
    binary search over the globally sorted (block, phi) key stream. O(log n)
    per query but O(1) extra memory; wins at very low block occupancy.

All jittable functions use static shapes with validity masks (TPU contract).
The unique passes (:func:`sorted_unique`, :func:`unique_pairs`) default to
sort-free Morton-radix counting (core/binning.py) with the argsort
baselines retained behind ``binning_mode='argsort'``.

Map representation ("kernel map", gather form — output stationary):
    kmap  : (N_out, K) int32  — input row feeding output i through tap k
                                 (-1 = no contribution)
plus, for the scatter-form layers (Gconv/Tconv, input stationary), triples
(in_idx, out_idx, tap) produced by the g* builders below. Both dataflows of
§V-A (output stationary for Subm3/Gconv2, input stationary for Gconv3/Tconv2)
are therefore expressible; :func:`strided_to_kmap` converts between them.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import binning, morton

INVALID = jnp.iinfo(jnp.int32).max


def _stable_order(codes: jnp.ndarray, nbits: int | None,
                  binning_mode: str) -> jnp.ndarray:
    """Stable ascending order of codes where INVALID marks invalid entries.

    ``binning_mode='counting'`` uses Morton-radix counting passes (no XLA
    sort primitive; requires the static bit budget ``nbits`` of valid
    codes); ``'argsort'`` is the retained global-sort baseline.
    """
    if binning_mode == "argsort" or nbits is None:
        return jnp.argsort(codes).astype(jnp.int32)
    if binning_mode != "counting":
        raise ValueError(f"unknown binning mode {binning_mode!r}")
    if nbits <= 30:
        # map the INVALID sentinel to the first out-of-budget value so the
        # radix only needs nbits + 1 passes-worth of key
        rk = jnp.where(codes == INVALID, jnp.int32(1 << nbits), codes)
        return binning.counting_argsort(rk, nbits + 1)
    # 31-bit budget: INVALID == int32 max already is the largest key
    return binning.counting_argsort(codes, 31)


class BlockTable(NamedTuple):
    """Stage-1 artifact of OCTENT (Fig. 5(c) lines 1-6): the octree table.

    ``banks`` is the (max_blocks * 8 * 512) flattened table T; entry -1 means
    empty. ``ublocks`` is the sorted, INVALID-padded list of occupied block
    keys — its rank is the table's block coordinate. The 8-bank SRAM of
    Fig. 6(a) becomes the middle axis; on TPU, querying all 8 banks at once
    is a single vectorized gather (the VPU is the parfor of line 9).

    Contract: the number of occupied blocks must be <= max_blocks; check
    ``n_blocks`` when sizing statically.
    """

    banks: jnp.ndarray      # (max_blocks * TABLE_SIZE,) int32
    ublocks: jnp.ndarray    # (max_blocks,) int32, sorted, INVALID padded
    n_blocks: jnp.ndarray   # () int32


def sorted_unique(codes: jnp.ndarray, size: int, *, nbits: int | None = None,
                  binning_mode: str = "counting"):
    """Sorted unique with static output ``size`` for int32 keys.

    Invalid inputs must be INVALID. Returns (uniq padded with INVALID,
    count, rank_of_each_input via searchsorted). jit-safe. ``nbits`` is the
    static bit budget of valid codes; with it the ordering pass is
    sort-free (Morton-radix counting, core/binning.py) — without it (or
    with ``binning_mode='argsort'``) the global argsort baseline runs.
    """
    order = _stable_order(codes, nbits, binning_mode)
    s = codes[order]
    is_new = jnp.concatenate([jnp.array([True]), s[1:] != s[:-1]]) & (s != INVALID)
    pos = jnp.cumsum(is_new) - 1
    uniq = jnp.full((size,), INVALID, dtype=codes.dtype)
    uniq = uniq.at[jnp.where(is_new, pos, size)].set(s, mode="drop")
    count = is_new.sum()
    rank = jnp.searchsorted(uniq, codes)
    return uniq, count, rank


def unique_pairs(hi: jnp.ndarray, lo: jnp.ndarray, valid: jnp.ndarray,
                 size: int, *, hi_bits: int | None = None,
                 lo_bits: int = morton.LOCAL_CODE_BITS,
                 binning_mode: str = "counting"):
    """Unique over lexicographic (hi, lo) int32 pair keys, no wide arithmetic.

    Avoids int64: composite voxel keys (block key << 12 | phi) can exceed 31
    bits, so uniqueness is established by a stable lexicographic order +
    neighbor comparison and ranks are scattered back through the
    permutation instead of being recovered by searchsorted. With the static
    bit budgets ``hi_bits``/``lo_bits`` the order comes from Morton-radix
    counting passes (no XLA sort primitive); without ``hi_bits`` — or with
    ``binning_mode='argsort'`` — the retained lexsort baseline runs.

    Returns (rep, count, rank): ``rep[r]`` is the original index of the
    representative of unique key r (-1 padding); ``rank[i]`` is the unique id
    of input i (== size for invalid inputs).
    """
    n = hi.shape[0]
    hi = jnp.where(valid, hi, INVALID)
    lo = jnp.where(valid, lo, INVALID)
    if (binning_mode == "argsort" or hi_bits is None or hi_bits > 30
            or lo_bits > 30):
        order = jnp.lexsort((lo, hi))
    else:
        # minor key first; invalid entries pushed past every valid hi key
        rlo = jnp.where(valid, lo, 0)
        rhi = jnp.where(valid, hi, jnp.int32(1 << hi_bits))
        order = binning.counting_lexsort((rlo, rhi),
                                         (lo_bits, hi_bits + 1))
    shi, slo, sval = hi[order], lo[order], valid[order]
    is_new = jnp.concatenate(
        [jnp.array([True]),
         (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])]) & sval
    pos = jnp.cumsum(is_new) - 1                      # unique id per sorted row
    count = is_new.sum()
    rank_sorted = jnp.where(sval, pos, size)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    rep = jnp.full((size,), -1, jnp.int32)
    rep = rep.at[jnp.where(is_new, pos, size)].set(order.astype(jnp.int32), mode="drop")
    return rep, count, rank


# ---------------------------------------------------------------------------
# Oracles / baselines
# ---------------------------------------------------------------------------

def build_kmap_bruteforce(coords: np.ndarray, batch: np.ndarray,
                          valid: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """O(N^2 K) traverse (Fig. 3(a)). Submanifold: outputs == inputs."""
    n = coords.shape[0]
    k = offsets.shape[0]
    kmap = np.full((n, k), -1, dtype=np.int32)
    for i in range(n):
        if not valid[i]:
            continue
        for t in range(k):
            target = coords[i] + offsets[t]
            for j in range(n):
                if valid[j] and batch[j] == batch[i] and np.all(coords[j] == target):
                    kmap[i, t] = j
                    break
    return kmap


def build_kmap_hash(coords: np.ndarray, batch: np.ndarray,
                    valid: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Serial hash probing — the GPU-engine baseline [9]. Host-side."""
    table = {}
    for j in range(coords.shape[0]):
        if valid[j]:
            table[(int(batch[j]),) + tuple(int(c) for c in coords[j])] = j
    n, k = coords.shape[0], offsets.shape[0]
    kmap = np.full((n, k), -1, dtype=np.int32)
    for i in range(n):
        if not valid[i]:
            continue
        for t in range(k):
            key = (int(batch[i]),) + tuple(int(c) for c in coords[i] + offsets[t])
            kmap[i, t] = table.get(key, -1)
    return kmap


# ---------------------------------------------------------------------------
# OCTENT stage 1: build the blockwise octree table (Fig. 5(c) lines 1-6)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_blocks", "grid_bits", "batch_bits",
                                   "binning_mode"))
def build_block_table(coords: jnp.ndarray, batch: jnp.ndarray,
                      valid: jnp.ndarray, *, max_blocks: int,
                      grid_bits: int = 7, batch_bits: int = 4,
                      binning_mode: str = "counting") -> BlockTable:
    n = coords.shape[0]
    bkey = jnp.where(valid, morton.block_key(coords, batch, grid_bits, batch_bits),
                     INVALID)
    ublocks, n_blocks, rank = sorted_unique(
        bkey, max_blocks, nbits=3 * grid_bits + batch_bits,
        binning_mode=binning_mode)
    phi = morton.local_code(coords)
    # flat layout [block, bank(phi_1), row(phi_hi)] — Fig. 6(a)'s banked SRAM
    bank, row = morton.bank_and_row(phi)
    flat = rank * morton.TABLE_SIZE + bank * morton.BANK_ROWS + row
    flat = jnp.where(valid & (rank < max_blocks), flat,
                     max_blocks * morton.TABLE_SIZE)
    banks = jnp.full((max_blocks * morton.TABLE_SIZE,), -1, dtype=jnp.int32)
    banks = banks.at[flat].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    return BlockTable(banks, ublocks, n_blocks)


# ---------------------------------------------------------------------------
# OCTENT stage 2: parallel query (Fig. 5(c) lines 7-13)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("grid_bits", "batch_bits"))
def query_block_table(table: BlockTable, qcoords: jnp.ndarray,
                      qbatch: jnp.ndarray, qvalid: jnp.ndarray, *,
                      grid_bits: int = 7, batch_bits: int = 4) -> jnp.ndarray:
    """Look up voxel indices for query coordinates (..., 3). Returns -1 miss.

    One gather resolves every query against every bank — the deserialized
    parfor. Negative / out-of-grid coordinates are rejected (the Query
    Transmitter's mask for PNELUT vacancies).
    """
    max_blocks = table.ublocks.shape[0]
    limit = (1 << grid_bits) * morton.BLOCK_SIZE
    inb = jnp.all((qcoords >= 0) & (qcoords < limit), axis=-1) & qvalid
    qc = jnp.clip(qcoords, 0, limit - 1)
    bkey = morton.block_key(qc, qbatch, grid_bits, batch_bits)
    brank = jnp.searchsorted(table.ublocks, bkey)
    brank_c = jnp.minimum(brank, max_blocks - 1)
    hit = inb & (table.ublocks[brank_c] == bkey)
    bank, row = morton.bank_and_row(morton.local_code(qc))
    flat = brank_c * morton.TABLE_SIZE + bank * morton.BANK_ROWS + row
    cand = table.banks[flat]
    return jnp.where(hit, cand, -1)


@partial(jax.jit, static_argnames=("max_blocks", "grid_bits", "batch_bits",
                                   "binning_mode"))
def build_kmap_octree(coords: jnp.ndarray, batch: jnp.ndarray,
                      valid: jnp.ndarray, offsets: jnp.ndarray, *,
                      max_blocks: int, grid_bits: int = 7,
                      batch_bits: int = 4,
                      binning_mode: str = "counting") -> jnp.ndarray:
    """OCTENT map search for submanifold convolution (outputs == inputs).

    Returns kmap (N, K) int32 with -1 for misses. This is the dense-table
    XLA builder, retained as the ``search_impl='xla'`` oracle of the fused
    engine (kernels/octent); ``binning_mode='argsort'`` additionally
    restores the pre-PR-3 global-argsort table build for baselines.
    """
    table = build_block_table(coords, batch, valid, max_blocks=max_blocks,
                              grid_bits=grid_bits, batch_bits=batch_bits,
                              binning_mode=binning_mode)
    q = coords[:, None, :] + offsets[None, :, :]            # (N, K, 3)
    qb = jnp.broadcast_to(batch[:, None], q.shape[:2])
    qv = jnp.broadcast_to(valid[:, None], q.shape[:2])
    return query_block_table(table, q, qb, qv,
                             grid_bits=grid_bits, batch_bits=batch_bits)


def sorted_key_fits(grid_bits: int, batch_bits: int) -> bool:
    """Whether the sorted-variant composite key (block << 12 | phi) fits
    int32 at these grid/batch widths. The single source of truth for the
    bit budget of :func:`build_kmap_sorted`."""
    return 3 * grid_bits + batch_bits + morton.LOCAL_CODE_BITS <= 31


@partial(jax.jit, static_argnames=("grid_bits", "batch_bits"))
def build_kmap_sorted(coords: jnp.ndarray, batch: jnp.ndarray,
                      valid: jnp.ndarray, offsets: jnp.ndarray, *,
                      grid_bits: int = 5, batch_bits: int = 4) -> jnp.ndarray:
    """Beyond-paper: table-free binary search over sorted (block<<12|phi) keys.

    Same output contract as :func:`build_kmap_octree`. Composite keys must
    fit int32 (3*grid_bits + batch_bits + 12 <= 31), i.e. grids up to
    512 voxels/axis at the defaults; use build_kmap_octree beyond that.
    """
    assert sorted_key_fits(grid_bits, batch_bits), (
        "sorted-key variant needs the composite key to fit int32; "
        "use build_kmap_octree for large grids")

    def composite(c, b, v):
        key = morton.block_key(c, b, grid_bits, batch_bits)
        key = (key << morton.LOCAL_CODE_BITS) | morton.local_code(c)
        return jnp.where(v, key, INVALID)

    keys = composite(coords, batch, valid)
    order = jnp.argsort(keys)
    skeys = keys[order]
    q = coords[:, None, :] + offsets[None, :, :]
    limit = (1 << grid_bits) * morton.BLOCK_SIZE
    inb = jnp.all((q >= 0) & (q < limit), axis=-1) & valid[:, None]
    qk = composite(jnp.clip(q, 0, limit - 1),
                   jnp.broadcast_to(batch[:, None], q.shape[:2]), inb)
    pos = jnp.searchsorted(skeys, qk)
    pos_c = jnp.minimum(pos, keys.shape[0] - 1)
    hit = inb & (skeys[pos_c] == qk) & (qk != INVALID)
    return jnp.where(hit, order[pos_c], -1)


# ---------------------------------------------------------------------------
# Strided layers: Gconv2 / Gconv3 / Tconv2 (paper §IV-D)
# ---------------------------------------------------------------------------

class StridedMaps(NamedTuple):
    """Scatter-form rulebook for strided/transposed layers.

    For Gconv: features flow in_idx -> out_idx through weight tap ``tap``.
    For Tconv2 the same structure is reused with roles swapped (§IV-D2).
    """

    out_coords: jnp.ndarray   # (N_out_max, 3) int32
    out_batch: jnp.ndarray    # (N_out_max,) int32
    out_valid: jnp.ndarray    # (N_out_max,) bool
    n_out: jnp.ndarray        # () int32 (clamped to the static budget)
    in_idx: jnp.ndarray       # (M,) int32
    out_idx: jnp.ndarray      # (M,) int32
    tap: jnp.ndarray          # (M,) int32 weight tap in [0, K^3)
    mvalid: jnp.ndarray       # (M,) bool
    # candidate-space accounting (builders with a static output budget —
    # Gconv3 — set these; budgetless builders leave the defaults):
    n_true: jnp.ndarray | None = None    # () int32 true unique-output count
    overflow: jnp.ndarray | None = None  # () bool: n_true > budget, i.e.
                                         # outputs were truncated


def _gather_rep(rep: jnp.ndarray, src: jnp.ndarray, fill=0):
    ok = rep >= 0
    out = jnp.take(src, jnp.maximum(rep, 0), axis=0)
    return jnp.where(ok if out.ndim == 1 else ok[:, None], out, fill), ok


@partial(jax.jit, static_argnames=("grid_bits", "batch_bits"))
@jax.named_scope("plan.search")
def build_maps_gconv2(coords: jnp.ndarray, batch: jnp.ndarray,
                      valid: jnp.ndarray, *, grid_bits: int = 7,
                      batch_bits: int = 4) -> StridedMaps:
    """Gconv2 (k=2, s=2): each voxel maps to its octree parent; the weight
    tap is the child octant phi_1 (§IV-D1: one-cycle PNELUT query).
    """
    n = coords.shape[0]
    parent = coords >> 1
    hi = morton.block_key(parent, batch, grid_bits, batch_bits)
    lo = morton.local_code(parent)
    rep, n_out, rank = unique_pairs(hi, lo, valid, n,
                                    hi_bits=3 * grid_bits + batch_bits)
    parents_all = parent
    out_coords, ok = _gather_rep(rep, parents_all)
    out_batch, _ = _gather_rep(rep, batch)
    tap = morton.child_octant(coords)
    return StridedMaps(
        out_coords=out_coords, out_batch=out_batch, out_valid=ok, n_out=n_out,
        in_idx=jnp.arange(n, dtype=jnp.int32),
        out_idx=jnp.where(valid, rank, 0).astype(jnp.int32),
        tap=tap.astype(jnp.int32), mvalid=valid)


def strided_out_extent(extent, kernel, stride, padding) -> tuple:
    """spconv's output extent of a strided conv, per axis:
    ``(D + 2p - K) // s + 1``."""
    return tuple((d + 2 * p - k) // s + 1
                 for d, k, s, p in zip(extent, kernel, stride, padding))


@partial(jax.jit, static_argnames=("grid_bits", "batch_bits", "out_budget",
                                   "kernel", "stride", "padding", "extent"))
@jax.named_scope("plan.search")
def build_maps_gconv3(coords: jnp.ndarray, batch: jnp.ndarray,
                      valid: jnp.ndarray, *, grid_bits: int = 7,
                      batch_bits: int = 4,
                      out_budget: int | None = None,
                      kernel: tuple = (3, 3, 3), stride: tuple = (2, 2, 2),
                      padding: tuple = (1, 1, 1),
                      extent: tuple | None = None) -> StridedMaps:
    """Strided SpConv maps, input-stationary (§IV-D3), with spconv's
    semantics per axis (x, y, z): input ``i`` feeds output ``o`` through
    kernel offset ``k`` in ``[0, K)`` iff ``o * s - p + k == i``, and
    ``o`` is kept where ``0 <= o`` and, given the input ``extent`` D,
    ``o < (D + 2p - K) // s + 1`` (:func:`strided_out_extent`). Taps are
    x-fastest, ``kx + Kx * (ky + Ky * kz)``.

    Per axis only offsets ``k = (i + p) mod s + j * s`` divide, so each
    input emits ``prod(ceil(K / s))`` (out, tap) candidates, enumerated
    statically: 8 for a 3x3x3 stride-2 conv, 2 for a 3-tap z-only one.
    The defaults (k3 s2 p1, no extent) are the paper's Gconv3,
    ``2 * o + d == i`` with ``d = k - 1``.
    """
    n = coords.shape[0]
    k_ax = np.asarray(kernel, np.int32)
    s_ax = np.asarray(stride, np.int32)
    p_ax = np.asarray(padding, np.int32)
    per_axis = [range(-(-int(k) // int(s))) for k, s in zip(k_ax, s_ax)]
    j = np.array([(jx, jy, jz) for jz in per_axis[2] for jy in per_axis[1]
                  for jx in per_axis[0]], np.int32)              # (C, 3)
    k = (coords + p_ax) % s_ax                                   # (N, 3)
    k = k[:, None, :] + j[None] * s_ax                           # (N, C, 3)
    out = (coords[:, None, :] + p_ax - k) // s_ax                # exact
    cand_ok = jnp.all((k < k_ax) & (out >= 0), axis=-1)
    if extent is not None:
        hi = np.asarray(strided_out_extent(extent, kernel, stride, padding))
        cand_ok &= jnp.all(out < hi, axis=-1)
    cand_ok = cand_ok & valid[:, None]
    tap = k[..., 0] + k_ax[0] * (k[..., 1] + k_ax[1] * k[..., 2])

    ob = jnp.broadcast_to(batch[:, None], out.shape[:2])
    hi = morton.block_key(out.reshape(-1, 3), ob.reshape(-1), grid_bits, batch_bits)
    lo = morton.local_code(out.reshape(-1, 3))
    ok_flat = cand_ok.reshape(-1)
    m = ok_flat.shape[0]                                 # C * N candidates
    # Static output budget: downsampled outputs number <= inputs in real
    # clouds, so callers cap the candidate space. Truncation is NOT
    # silent: ``n_true`` reports the true unique-output count and
    # ``overflow`` flags n_true > budget, which plan.gconv3_plan
    # surfaces exactly like the octree block-table overflow (eager
    # CapacityOverflow raise / ConvPlan.overflow under jit).
    budget = out_budget if out_budget is not None else m
    rep, n_out, rank = unique_pairs(hi, lo, ok_flat, budget,
                                    hi_bits=3 * grid_bits + batch_bits)
    n_true = n_out.astype(jnp.int32)
    ok_flat = ok_flat & (rank < budget)
    out_coords, okv = _gather_rep(rep, out.reshape(-1, 3))
    out_batch, _ = _gather_rep(rep, ob.reshape(-1))
    return StridedMaps(
        out_coords=out_coords, out_batch=out_batch, out_valid=okv,
        n_out=jnp.minimum(n_out, budget),
        in_idx=jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                                (n, j.shape[0])).reshape(-1),
        out_idx=jnp.where(ok_flat, rank, 0).astype(jnp.int32),
        tap=tap.reshape(-1).astype(jnp.int32), mvalid=ok_flat,
        n_true=n_true, overflow=n_true > budget)


@jax.jit
@jax.named_scope("plan.search")
def transpose_maps(maps: StridedMaps, target_coords: jnp.ndarray,
                   target_batch: jnp.ndarray,
                   target_valid: jnp.ndarray) -> StridedMaps:
    """Tconv2: reuse M_Gconv2 with in/out swapped (§IV-D2 — the exported map
    is reloaded into the Map Table rather than re-searched)."""
    return StridedMaps(
        out_coords=target_coords, out_batch=target_batch,
        out_valid=target_valid, n_out=target_valid.sum(),
        in_idx=maps.out_idx, out_idx=maps.in_idx, tap=maps.tap,
        mvalid=maps.mvalid)


@partial(jax.jit, static_argnames=("n_out", "n_taps"))
@jax.named_scope("plan.search")
def strided_to_kmap(maps: StridedMaps, *, n_out: int, n_taps: int) -> jnp.ndarray:
    """Convert scatter triples to gather-form kmap (n_out, n_taps).

    Valid whenever each (out, tap) cell has at most one contributor — true
    for all SpConv layer types (an output site sees one input per tap).
    This switches the dataflow from input- to output-stationary (§V-A).
    """
    flat = maps.out_idx * n_taps + maps.tap
    flat = jnp.where(maps.mvalid, flat, n_out * n_taps)
    kmap = jnp.full((n_out * n_taps,), -1, dtype=jnp.int32)
    kmap = kmap.at[flat].set(maps.in_idx, mode="drop")
    return kmap.reshape(n_out, n_taps)
