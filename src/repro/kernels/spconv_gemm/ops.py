"""jit'd wrappers: kmap -> output-blocked tap tiles -> fused kernel.

``build_tap_tiles`` is the Top Control Unit of Fig. 4 in data-parallel form:
it turns the (N_out, K) kernel map into bm-padded gather/scatter streams
plus the scalar-prefetch metadata the kernel needs. The layout is
**output-block-major, tap-minor** (DESIGN.md §5): maps are grouped by the
bo-row output block of their target, and within a block the tap segments
are laid out hottest-first (rulebook.tap_schedule, §V-C). Every tile is
single-tap and single-output-block, so the kernel can keep the tap's weight
block VMEM-resident across a tap run *and* accumulate a block's partial
sums on chip across its whole run of tiles (output-stationary, §V-A).
Contiguous gather-index runs are detected here and recorded as per-tile
metadata (``tile_run`` for whole-tile runs, ``grp_contig``/``grp_skip``
bitmasks at GRP-slot granularity) so the kernel batches them into single
strided DMAs.

Execution comes in two forms (DESIGN.md §5, §6):

  * :func:`apply_kmap`       — materialized gather: an (M_pad, Cin) gathered
    copy of the features is built in HBM and fed to ``spconv_gemm``, with an
    XLA scatter-add after. Kept as the comparison baseline.
  * :func:`apply_kmap_fused` / :func:`apply_tiles` — gather-fused,
    output-stationary: the kernel pulls rows straight from the full feature
    array via double-buffered DMAs and scatter-adds in-kernel
    (``spconv_gemm_fused``); neither the gathered intermediate nor the
    (M_pad, Cout) partial products ever exist. ``apply_tiles`` additionally
    accepts pre-built geometry tiles so a cached ConvPlan (core/plan.py)
    can skip the whole sort/pad stage and only refresh tile liveness per
    layer, and it picks the Cin block size ``bk`` from the DESIGN.md §6
    VMEM budget automatically.

The identical machinery drives ragged MoE dispatch (models/moe.py) — the
paper's rulebook *is* an expert-dispatch table (DESIGN.md §5).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import rulebook as _rulebook
from repro.core import sparsity as _sparsity
from repro.kernels.spconv_gemm.kernel import (GRP, LANE, spconv_gemm,
                                              spconv_gemm_fused)
from repro.kernels.spconv_gemm.ref import (spconv_gemm_fused_ref,
                                           spconv_gemm_ref)

#: VMEM working-set budget for the fused kernel (DESIGN.md §6): rows double
#: buffer + weight block + f32 accumulator + resident output block.
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def kernel_impl() -> str:
    """pallas | interpret | ref — resolved once per call site from
    ``REPRO_KERNEL_IMPL`` (documented in runtime/flags.py).

    Resolve this *outside* jit boundaries (the public wrappers below do):
    the env var must be re-read per call, not frozen into a trace cache key.
    """
    impl = os.environ.get("REPRO_KERNEL_IMPL", "auto")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def hardware_impl() -> str:
    """The impl that exercises the Pallas kernel on this host: the compiled
    kernel on TPU, the interpreter elsewhere. Used by tests/benchmarks so
    the tier-1 suite runs on CPU without a TPU present."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def spac_block_enabled() -> bool:
    """Cin-block-grain SPAC toggle (``REPRO_SPAC_BLOCK``, runtime/flags.py).

    Re-read per call like kernel_impl(); '0' drops the fused kernel back to
    tile-grain skipping (the pre-§14 behavior) — output is identical either
    way, only the elided DMA/MAC work changes."""
    return os.environ.get("REPRO_SPAC_BLOCK", "1") != "0"


class TapTiles(NamedTuple):
    """Output-blocked, tap-scheduled tile streams plus run metadata.

    All per-slot arrays are (M_pad,), all per-tile arrays (T,) with
    T = M_pad / bm. ``bo`` is the static output-block height the layout was
    built for (a plain int: it never crosses a jit boundary — execution
    configs carry it as a static).
    """
    gather_idx: jnp.ndarray    # source row per map slot (0 for pad)
    scatter_idx: jnp.ndarray   # output row per map slot (n_out_pad for pad
                               # — outside every output block, see build)
    slot_valid: jnp.ndarray    # bool
    tile_tap: jnp.ndarray      # weight tap per m-tile
    tile_nz: jnp.ndarray       # 0 => tile skippable
    tile_ob: jnp.ndarray       # output block per m-tile (monotone)
    tile_first: jnp.ndarray    # 1 => opens its output block's run
    tile_run: jnp.ndarray      # 1 => whole tile is one contiguous gather run
    grp_skip: jnp.ndarray      # bitmask: GRP-group has no valid slot
    grp_contig: jnp.ndarray    # bitmask: GRP-group is one contiguous run
    bo: int                    # static output block rows

    @property
    def bm(self) -> int:
        return self.gather_idx.shape[0] // self.tile_tap.shape[0]

    @property
    def n_tiles(self) -> int:
        return self.tile_tap.shape[0]


def _padded_budget(n_out: int, k: int, bm: int, bo: int) -> int:
    # every (output block, tap) group may waste up to bm-1 slots to padding,
    # and empty output blocks force one all-pad tile each so the kernel
    # still opens (zeroes) their block
    n_blocks = -(-n_out // bo)
    return ((n_out * k + n_blocks * k * (bm - 1)) // bm + 1 + n_blocks) * bm


def build_tap_tiles(kmap: jnp.ndarray, row_nz: jnp.ndarray | None = None,
                    *, bm: int = 128, bo: int | None = None,
                    schedule: bool = True,
                    binning: str = "counting") -> TapTiles:
    """Sort maps by (output block, scheduled tap), pad each group to bm.

    ``bo`` is the output-block height of the output-stationary layout;
    every tile's valid slots target rows of one bo-row block, so the fused
    kernel can scatter locally. None picks ``max(bm, 512)`` — taller blocks
    amortize the per-(block, tap) tile padding (each group wastes up to
    bm-1 slots) while a (bo, Cout) block still fits the §6 VMEM budget.

    ``schedule=True`` orders each block's tap segments hottest-first
    (rulebook.tap_schedule): within a block the tile stream visits
    high-map-count taps in one run each, and consecutive blocks meet on the
    hottest tap, so the kernel's tap-indexed weight block stays
    VMEM-resident longest (§V-C). ``tile_tap`` always carries the *actual*
    tap id per tile, whatever the segment order.

    ``row_nz`` enables SPAC row elision: maps sourcing all-zero rows are
    dropped before tiling, shrinking the *live* map stream exactly like the
    ASIC's Gather Unit shrinks operand vectors. Leave it None when building
    geometry-only tiles for a cached plan and refresh liveness per layer
    with :func:`tile_liveness` instead.

    ``binning`` selects how slots are placed (DESIGN.md §5). The default
    ``'counting'`` places every slot by reading, with no XLA ``sort``, no
    scatter and no per-map gather over the n_out*K map stream: group
    counts are dense reductions over a (block, tap, row) view of the kmap,
    a map's stable within-group rank is a cumsum down its column (exactly
    one map per (output row, tap) makes that the counting rank), and each
    single-(block, tap) tile fetches its group's bo-long column once and
    picks the map of each of its bm slots by compare-and-select.
    ``'argsort'`` is the retained baseline (bincounts, a 27N-key global
    argsort and three permutation scatters); both produce bit-identical
    tiles (tested).
    """
    if bo is None:
        bo = max(bm, 512)
    arrays = _build_tap_tiles(kmap, row_nz, bm=bm, bo=bo, schedule=schedule,
                              binning=binning)
    return TapTiles(*arrays, bo=bo)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bo", "schedule", "binning"))
@jax.named_scope("plan.tiles")
def _build_tap_tiles(kmap, row_nz, *, bm, bo, schedule, binning):
    n_out, k = kmap.shape
    m_pad = _padded_budget(n_out, k, bm, bo)
    grp = GRP if bm % GRP == 0 else bm
    n_grp = bm // grp
    assert n_grp <= 32, (bm, grp)
    t = m_pad // bm
    if binning == "argsort":
        gather, scatter, svalid, tile_tap, tile_ob = _layout_argsort(
            kmap, row_nz, bm=bm, bo=bo, schedule=schedule, m_pad=m_pad)
    elif binning == "counting":
        gather, scatter, svalid, tile_tap, tile_ob = _layout_tile_major(
            kmap, row_nz, bm=bm, bo=bo, schedule=schedule, m_pad=m_pad)
    else:
        raise ValueError(f"unknown binning mode {binning!r}")

    v2 = svalid.reshape(t, bm)
    tile_nz = v2.any(axis=1).astype(jnp.int32)
    tile_first = jnp.concatenate(
        [jnp.ones(1, jnp.int32),
         (tile_ob[1:] != tile_ob[:-1]).astype(jnp.int32)])

    # gather-run metadata: successive-slot contiguity, summarized per tile
    # and per GRP-slot group so the kernel batches runs into strided DMAs
    g2 = gather.reshape(t, bm)
    nxt = (g2[:, 1:] == g2[:, :-1] + 1) & v2[:, 1:] & v2[:, :-1]
    tile_run = (v2.all(axis=1) & nxt.all(axis=1)).astype(jnp.int32)
    pair3 = jnp.concatenate([nxt, jnp.ones((t, 1), bool)],
                            axis=1).reshape(t, n_grp, grp)[..., :grp - 1]
    v3 = v2.reshape(t, n_grp, grp)
    bits = (1 << jnp.arange(n_grp, dtype=jnp.int32))
    grp_contig = ((v3.all(-1) & pair3.all(-1)).astype(jnp.int32)
                  * bits).sum(-1).astype(jnp.int32)
    grp_skip = ((~v3.any(-1)).astype(jnp.int32) * bits).sum(-1).astype(
        jnp.int32)
    return (gather, scatter, svalid, tile_tap, tile_nz, tile_ob, tile_first,
            tile_run, grp_skip, grp_contig)


def _group_starts(counts_g, *, n_blocks, k, bm):
    """Padded group starts, (n_blocks*K + 1,). Each (block, schedule-slot)
    group is padded to a bm multiple; an empty output block forces one
    all-pad tile on its leading group so the kernel still opens (zeroes)
    the block."""
    pc2 = (((counts_g + bm - 1) // bm) * bm).reshape(n_blocks, k)
    lead = jnp.arange(k) == 0
    pc2 = pc2 + jnp.where((pc2.sum(1, keepdims=True) == 0) & lead, bm, 0)
    return jnp.concatenate([jnp.zeros(1, pc2.dtype),
                            jnp.cumsum(pc2.reshape(-1))])


def _tile_groups(pstarts, sched, *, k, bm, m_pad):
    """Per m-tile: its group (clamped to the last), tap and output block.
    Tiles past the last group's padding belong to no group; they come out
    as the last group's, holding only pad slots."""
    g_total = pstarts.shape[0] - 1
    tile_starts = jnp.arange(m_pad // bm) * bm
    grank = jnp.searchsorted(pstarts[1:], tile_starts, side="right")
    capped = jnp.minimum(grank, g_total - 1)
    tile_tap = sched[capped % k].astype(jnp.int32)
    tile_ob = (capped // k).astype(jnp.int32)
    return capped, tile_tap, tile_ob


def _layout_tile_major(kmap, row_nz, *, bm, bo, schedule, m_pad):
    """Default layout: every slot is placed by reading, with no scatter
    and no per-map gather over the n_out*K map stream.

    Counts come from dense reductions over a (block, tap, row) view of the
    kmap. Every m-tile is single-(block, tap) by construction, so tile t
    fetches its group's bo-long column once and resolves each of its bm
    slots by compare-and-select: slot p0 + j takes the unique row whose
    valid map has within-group rank p0 + j + 1.
    """
    n_out, k = kmap.shape
    n_blocks = -(-n_out // bo)
    g_total = n_blocks * k
    # cols[b, tap, i] = kmap[b*bo + i, tap]; rows past n_out hold -1
    cols = jnp.pad(kmap, ((0, n_blocks * bo - n_out), (0, 0)),
                   constant_values=-1)
    cols = cols.reshape(n_blocks, bo, k).transpose(0, 2, 1)
    valid = cols >= 0
    if row_nz is not None:
        valid &= jnp.take(row_nz, jnp.maximum(cols, 0))
    # each output row holds exactly one map per tap, so a map's stable rank
    # within its (block, tap) group is the count of valid entries on earlier
    # rows of the block: an inclusive cumsum along the column
    csum = jnp.cumsum(valid.astype(jnp.int32), axis=-1)
    counts_bt = csum[..., -1]                           # (n_blocks, K)
    if schedule:
        sched = _rulebook.tap_schedule(counts_bt.sum(0))  # tap ids, hot first
    else:
        sched = jnp.arange(k, dtype=jnp.int32)
    counts_g = jnp.take(counts_bt, sched, axis=1).reshape(-1)
    pstarts = _group_starts(counts_g, n_blocks=n_blocks, k=k, bm=bm)
    capped, tile_tap, tile_ob = _tile_groups(pstarts, sched, k=k, bm=bm,
                                             m_pad=m_pad)

    col = tile_ob * k + tile_tap               # row of the (g_total, bo) views
    rank = ((jnp.arange(m_pad // bm) * bm - pstarts[capped])[:, None]
            + jnp.arange(1, bm + 1)[None, :])                   # (T, bm)
    key = jnp.where(valid, csum, 0).reshape(g_total, bo)[col]   # (T, bo)
    src = cols.reshape(g_total, bo)[col]
    hit = key[:, None, :] == rank[:, :, None]                   # (T, bm, bo)
    gather = jnp.where(hit, src[:, None, :], 0).sum(-1)
    row = jnp.where(hit, jnp.arange(bo)[None, None, :], 0).sum(-1)
    svalid = rank <= counts_g[capped][:, None]
    # drop target for pad/elided slots: n_out_pad sits OUTSIDE every bo-row
    # output block (blocks tile [0, n_blocks*bo)), so the kernel's in-block
    # mask always zeroes such slots before the one-hot matmul — their rows
    # may be unfetched (garbage) VMEM; n_out itself can fall *inside* the
    # last block when bo does not divide n_out. The XLA paths drop it via
    # scatter mode="drop" just the same.
    scatter = jnp.where(svalid, tile_ob[:, None] * bo + row, n_blocks * bo)
    return (gather.reshape(-1), scatter.reshape(-1), svalid.reshape(-1),
            tile_tap, tile_ob)


def _layout_argsort(kmap, row_nz, *, bm, bo, schedule, m_pad):
    """Retained baseline and oracle of :func:`_layout_tile_major`: bincounts
    of the per-map (block, schedule rank) keys, a global stable argsort of
    them, then three permutation scatters over the slot budget."""
    n_out, k = kmap.shape
    n_blocks = -(-n_out // bo)
    g_total = n_blocks * k
    flat_in = kmap.reshape(-1)
    taps = jnp.tile(jnp.arange(k, dtype=jnp.int32), n_out)
    outs = jnp.repeat(jnp.arange(n_out, dtype=jnp.int32), k)
    valid = flat_in >= 0
    if row_nz is not None:
        valid &= jnp.take(row_nz, jnp.maximum(flat_in, 0))

    counts = jnp.bincount(jnp.where(valid, taps, k), length=k + 1)[:k]
    if schedule:
        sched = _rulebook.tap_schedule(counts)          # tap ids, hot first
    else:
        sched = jnp.arange(k, dtype=jnp.int32)
    srank = jnp.zeros((k,), jnp.int32).at[sched].set(
        jnp.arange(k, dtype=jnp.int32))                 # tap -> schedule rank
    # group key: output block major, schedule rank minor; invalid at the end
    gkey = jnp.where(valid, (outs // bo) * k + srank[taps], g_total)
    counts_g = jnp.bincount(gkey, length=g_total + 1)[:g_total]
    order = jnp.argsort(gkey, stable=True)
    skey = gkey[order]
    gstarts = jnp.concatenate([jnp.zeros(1, counts_g.dtype),
                               jnp.cumsum(counts_g)])[:g_total]
    capped = jnp.minimum(skey, g_total - 1)
    rank = jnp.arange(n_out * k) - jnp.take(gstarts, capped)
    pstarts = _group_starts(counts_g, n_blocks=n_blocks, k=k, bm=bm)
    slot = jnp.where(skey < g_total, jnp.take(pstarts, capped) + rank, m_pad)

    gather = jnp.zeros((m_pad,), jnp.int32).at[slot].set(
        jnp.maximum(flat_in[order], 0), mode="drop")
    scatter = jnp.full((m_pad,), n_blocks * bo, jnp.int32).at[slot].set(
        outs[order], mode="drop")
    svalid = jnp.zeros((m_pad,), bool).at[slot].set(valid[order],
                                                    mode="drop")
    _, tile_tap, tile_ob = _tile_groups(pstarts, sched, k=k, bm=bm,
                                        m_pad=m_pad)
    return gather, scatter, svalid, tile_tap, tile_ob


def tile_liveness(tiles: TapTiles, row_nz: jnp.ndarray) -> jnp.ndarray:
    """Refresh per-tile skip flags against the *current* features.

    Geometry tiles are feature-independent and cacheable across layers; the
    SPAC skip mask is not (the post-ReLU zero pattern changes every layer).
    A slot is live iff its map is valid and its source row has any nonzero;
    a tile is skippable iff no slot in it is live. Maps to zero rows that
    sit inside a live tile contribute exactly 0 — elision stays lossless.
    """
    live = tiles.slot_valid & jnp.take(row_nz, tiles.gather_idx)
    return live.reshape(-1, tiles.bm).any(axis=1).astype(jnp.int32)


def tile_block_liveness(tiles: TapTiles, blk_nz: jnp.ndarray) -> jnp.ndarray:
    """(T, n_k) per-(tile, Cin-block) skip flags from per-row block liveness.

    ``blk_nz`` is (N, Cin/bk) bool (sparsity.row_block_nonzero, or threaded
    from the previous layer's fused epilogue via ActSparsity.block_liveness).
    A (tile, Cin-block) pair is dead iff every valid slot's bk-slice is
    exactly zero — the fused kernel then skips both the gather DMA and the
    MAC of that block (DESIGN.md §14). Callers must keep ``blk_nz``
    consistent with the ``row_nz`` used for tile liveness (AND it with
    ``row_nz[:, None]``) so a live block never outlives its tile.
    """
    live = tiles.slot_valid[:, None] & jnp.take(blk_nz, tiles.gather_idx,
                                                axis=0)
    n_k = blk_nz.shape[1]
    return live.reshape(tiles.n_tiles, tiles.bm, n_k).any(axis=1).astype(
        jnp.int32)


def refresh_liveness(tiles: TapTiles, row_nz: jnp.ndarray,
                     blk_nz: jnp.ndarray | None = None, *, n_k: int = 1,
                     src_valid: jnp.ndarray | None = None):
    """``(tile_nz, tile_bk_nz)``: the SPAC skip flags of one layer.

    The refresh (:func:`tile_liveness`, and :func:`tile_block_liveness`
    when ``blk_nz`` is given) gathers a liveness bit for every map slot.
    It can differ from the geometry flags the tiles already hold
    (``tiles.tile_nz``) only where a valid slot reads a dead row. With
    ``src_valid``, the (N,) mask of the rows a valid slot may read (for a
    Subm3 plan the ``valid`` it was built from: its search table holds
    only valid voxels), one O(N) reduction checks that every such row is
    live, and ``lax.cond`` then takes the geometry flags and skips the
    gather. Both branches give the same flags whenever the check holds,
    so the kernel's work and the answer do not change; a dead valid row
    still takes the full refresh. Each call notes its "full" flag with
    :func:`repro.core.sparsity.note_refresh`.
    """
    def full():
        tile_nz = tile_liveness(tiles, row_nz)
        if blk_nz is None:
            return tile_nz, jnp.repeat(tile_nz[:, None], n_k, axis=1)
        return tile_nz, tile_block_liveness(tiles, blk_nz)

    if src_valid is None:
        _sparsity.note_refresh(True)
        return full()

    def geometry():
        return tiles.tile_nz, jnp.repeat(tiles.tile_nz[:, None], n_k, axis=1)

    # blk_nz is ANDed with row_nz, so its check implies the row check
    all_live = (jnp.all(row_nz | ~src_valid) if blk_nz is None
                else jnp.all(blk_nz | ~src_valid[:, None]))
    _sparsity.note_refresh(~all_live)
    return jax.lax.cond(all_live, geometry, full)


def pick_bk(c_in: int, *, bm: int, bn: int, bo: int, c_out: int,
            budget_bytes: int = VMEM_BUDGET_BYTES) -> int:
    """Largest Cin block dividing ``c_in`` that keeps the fused kernel's
    §6 working set in budget: double-buffered rows (2*bm*bk), the weight
    block (bk*bn), the f32 accumulator (bm*c_out) and the resident output
    block (bo*c_out). Caps bk at 512 (the old whole-Cin residency limit) so
    wide backbones stop relying on whole-Cin VMEM residency; a block short
    of all Cin must be a LANE multiple (the kernel DMAs whole lane tiles);
    falls back to whole-Cin when nothing fits."""
    fixed = 4 * (bm * c_out + bo * c_out)
    for bk in sorted((d for d in range(1, c_in + 1) if c_in % d == 0),
                     reverse=True):
        if bk > 512 or (bk != c_in and bk % LANE):
            continue
        if fixed + 4 * (2 * bm * bk + bk * bn) <= budget_bytes:
            return bk
    return c_in


def _pad_cout(weights: jnp.ndarray, bn: int) -> jnp.ndarray:
    """Zero-pad the Cout axis to a bn multiple (kernel lane contract);
    callers slice the output back to the true Cout."""
    c_out = weights.shape[-1]
    c_pad = -(-c_out // bn) * bn
    if c_pad == c_out:
        return weights
    return jnp.pad(weights, ((0, 0), (0, 0), (0, c_pad - c_out)))


def _exec_ref_math(feats, w, gather_idx, tile_tap, tile_nz, scatter_idx,
                   *, n_out, bm, bn):
    """Differentiable pure-XLA math of the fused execution (pre-bias).

    Mathematically identical to the output-stationary kernel on the first
    n_out rows: both add, per valid slot, feats[gather] @ W[tap] into
    out[scatter]; padding lands in the drop row here and in sliced-off
    block-pad rows there."""
    ps = spconv_gemm_fused_ref(feats, w, gather_idx, tile_tap, tile_nz,
                               bm=bm, bn=bn)
    out = jnp.zeros((n_out + 1, w.shape[-1]), ps.dtype)
    return out.at[scatter_idx].add(ps, mode="drop")[:n_out]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exec_fused(cfg, feats, w, gather_idx, tile_tap, tile_nz, tile_bk_nz,
                tile_nz_geo, scatter_idx, tile_ob, tile_first, tile_run,
                grp_skip, grp_contig):
    """Fused execution (kernel or oracle) with the SPAC-correct backward.

    ``tile_nz`` is the feature-refreshed (elided) liveness driving the
    forward skips; ``tile_nz_geo`` is the geometry-only liveness. Elision
    is forward-only lossless (DESIGN.md §2): a zero row contributes exactly
    0, but d(out)/d(feats) of that row is wᵀ·g — so the backward
    re-derives through the *un-elided* oracle math. The pre-fix code
    replayed the VJP through ``tile_nz`` and silently zeroed ``dfeats``
    for every exactly-zero row. cfg = (n_out, n_out_pad, bm, bn, bo, bk,
    impl) — hashable, impl in ('pallas', 'interpret', 'ref').
    """
    n_out, n_out_pad, bm, bn, bo, bk, impl = cfg
    if impl == "ref":
        return _exec_ref_math(feats, w, gather_idx, tile_tap, tile_nz,
                              scatter_idx, n_out=n_out, bm=bm, bn=bn)
    out = spconv_gemm_fused(feats, w, gather_idx, scatter_idx, tile_tap,
                            tile_nz, tile_ob, tile_first, tile_run,
                            grp_skip, grp_contig, tile_bk_nz=tile_bk_nz,
                            bm=bm, bn=bn, bo=bo, bk=bk, n_out_pad=n_out_pad,
                            interpret=impl == "interpret")
    return out[:n_out]


def _exec_fused_fwd(cfg, feats, w, gather_idx, tile_tap, tile_nz, tile_bk_nz,
                    tile_nz_geo, scatter_idx, tile_ob, tile_first, tile_run,
                    grp_skip, grp_contig):
    out = _exec_fused(cfg, feats, w, gather_idx, tile_tap, tile_nz,
                      tile_bk_nz, tile_nz_geo, scatter_idx, tile_ob,
                      tile_first, tile_run, grp_skip, grp_contig)
    return out, (feats, w, gather_idx, tile_tap, tile_nz, tile_bk_nz,
                 tile_nz_geo, scatter_idx, tile_ob, tile_first, tile_run,
                 grp_skip, grp_contig)


def _exec_fused_bwd(cfg, res, g):
    n_out, _, bm, bn, *_ = cfg
    (feats, w, gather_idx, tile_tap, tile_nz, tile_bk_nz, tile_nz_geo,
     scatter_idx, *ints) = res
    # geometry liveness, NOT the elided tile_nz: see _exec_fused docstring
    _, vjp = jax.vjp(
        lambda f, ww: _exec_ref_math(f, ww, gather_idx, tile_tap,
                                     tile_nz_geo, scatter_idx, n_out=n_out,
                                     bm=bm, bn=bn),
        feats, w)
    dfeats, dw = vjp(g)
    zeros_i32 = [np.zeros(a.shape, jax.dtypes.float0)
                 for a in (gather_idx, tile_tap, tile_nz, tile_bk_nz,
                           tile_nz_geo, scatter_idx, *ints)]
    return (dfeats, dw, *zeros_i32)


_exec_fused.defvjp(_exec_fused_fwd, _exec_fused_bwd)


class FusedEpilogue(NamedTuple):
    """BN-inference + ReLU folded into the fused kernel (DESIGN.md §14).

    ``y = relu(out * scale + shift)`` applied to each finished output block
    while it is still VMEM-resident, masked to zero on invalid rows.
    Inference-only: differentiating through it raises (the pre-activation
    output is never materialized). Build scale/shift with
    spconv.fold_bn_inference — the conv bias folds into ``shift``, so pass
    ``bias=None`` alongside.
    """
    scale: jnp.ndarray   # (Cout,) float32
    shift: jnp.ndarray   # (Cout,) float32
    valid: jnp.ndarray   # (n_out,) bool


def _epilogue_math(out, scale, shift, valid, bn):
    """XLA mirror of the in-kernel epilogue: same op order (f32 affine,
    ReLU, valid mask, dtype cast) and the per-(row, bn-group) liveness
    computed AFTER the cast, so the emitted masks are exactly a fresh
    sweep of the returned output. The affine itself may differ from the
    in-kernel result by an ulp (fused multiply-add rounding) — masks stay
    self-consistent per path either way."""
    y = (out.astype(jnp.float32) * scale[None, :].astype(jnp.float32)
         + shift[None, :].astype(jnp.float32))
    y = jnp.where(valid[:, None], jnp.maximum(y, 0.0), 0.0)
    yc = y.astype(out.dtype)
    n, c = yc.shape
    g = -(-c // bn)
    f = jnp.pad(yc, ((0, 0), (0, g * bn - c))) if g * bn != c else yc
    blk_nz = jnp.any(f.reshape(n, g, bn) != 0, axis=-1)
    return yc, blk_nz


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _epi_xla(bn, out, scale, shift, valid):
    return _epilogue_math(out, scale, shift, valid, bn)


def _epi_xla_fwd(bn, out, scale, shift, valid):
    return _epi_xla(bn, out, scale, shift, valid), ()


def _epi_xla_bwd(bn, res, g):
    raise NotImplementedError(
        "the fused BN/ReLU epilogue is inference-only: its backward would "
        "differentiate through elided activation state. For training, "
        "compose subm_conv3 + batch_norm + relu unfused.")


_epi_xla.defvjp(_epi_xla_fwd, _epi_xla_bwd)


def apply_epilogue_xla(out: jnp.ndarray, epilogue: FusedEpilogue, *,
                       bn: int = 128):
    """Apply a FusedEpilogue outside the kernel (the impl='xla' path).

    Returns ``(y, ActSparsity)`` exactly matching what the in-kernel
    epilogue emits. Inference-only (differentiation raises), like the
    kernel path."""
    yc, blk_nz = _epi_xla(bn, out, epilogue.scale, epilogue.shift,
                          epilogue.valid)
    return yc, _sparsity.ActSparsity(row_nz=blk_nz.any(-1), blk_nz=blk_nz,
                                     blk=bn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exec_fused_epi(cfg, feats, w, scale, shift, valid_pad, gather_idx,
                    tile_tap, tile_nz, tile_bk_nz, scatter_idx, tile_ob,
                    tile_first, tile_run, grp_skip, grp_contig):
    """Fused execution + in-kernel BN/ReLU epilogue and activation-sparsity
    emission. Returns (out[:n_out], nz[:n_out]) where nz is the int32
    per-(row, bn-group) liveness of the *next* layer's input. scale/shift
    are Cout-padded f32; valid_pad is (n_out_pad,). Inference-only."""
    n_out, n_out_pad, bm, bn, bo, bk, impl = cfg
    if impl == "ref":
        out = _exec_ref_math(feats, w, gather_idx, tile_tap, tile_nz,
                             scatter_idx, n_out=n_out, bm=bm, bn=bn)
        yc, blk_nz = _epilogue_math(out, scale, shift, valid_pad[:n_out], bn)
        return yc, blk_nz.astype(jnp.int32)
    out, nz = spconv_gemm_fused(feats, w, gather_idx, scatter_idx, tile_tap,
                                tile_nz, tile_ob, tile_first, tile_run,
                                grp_skip, grp_contig, tile_bk_nz=tile_bk_nz,
                                epi_scale=scale, epi_shift=shift,
                                epi_valid=valid_pad, bm=bm, bn=bn, bo=bo,
                                bk=bk, n_out_pad=n_out_pad, epilogue=True,
                                interpret=impl == "interpret")
    return out[:n_out], nz[:n_out]


def _exec_fused_epi_fwd(cfg, *args):
    return _exec_fused_epi(cfg, *args), ()


def _exec_fused_epi_bwd(cfg, res, g):
    raise NotImplementedError(
        "the fused BN/ReLU epilogue is inference-only: its backward would "
        "differentiate through elided activation state. For training, "
        "compose subm_conv3 + batch_norm + relu unfused.")


_exec_fused_epi.defvjp(_exec_fused_epi_fwd, _exec_fused_epi_bwd)


def apply_tiles(feats: jnp.ndarray, weights: jnp.ndarray, tiles: TapTiles,
                bias: jnp.ndarray | None = None, *, n_out: int,
                row_nz: jnp.ndarray | None = None,
                act: "_sparsity.ActSparsity | None" = None,
                epilogue: FusedEpilogue | None = None, bn: int = 128,
                bk: int | None = None, impl: str | None = None,
                src_valid: jnp.ndarray | None = None):
    """Execute a rulebook from pre-built tiles (the ConvPlan hot path).

    feats stays un-gathered; the output-stationary fused kernel (or its
    oracle) pulls rows by ``tiles.gather_idx`` and scatter-adds in-kernel.
    ``row_nz`` refreshes tile liveness for SPAC; ``act`` threads the
    previous layer's epilogue-emitted ActSparsity instead (row grain plus,
    when its groups align with this layer's Cin blocking, block grain
    without any HBM re-sweep); when both are None the build-time geometry
    ``tile_nz`` is used as-is. Cin-block-grain skipping inside live tiles
    engages whenever liveness is available and ``REPRO_SPAC_BLOCK`` is on.
    C_out is zero-padded to a bn multiple for the kernel and sliced back
    afterwards; the Cin block ``bk`` is picked from the DESIGN.md §6 VMEM
    budget unless given.

    ``src_valid`` (N,) bool marks the rows a valid slot may read; every
    ``kmap`` entry >= 0 must point at one (true of a Subm3 plan and its
    ``valid``). With it the refresh short-circuits to the geometry flags
    when every such row is live (:func:`refresh_liveness`): one O(N)
    check in place of a gather over all M_pad slots, exact because a
    refresh can only clear a tile whose valid slots all read dead rows.
    A dead valid row, or no ``src_valid``, takes the full refresh. With
    trained weights that leave a few dead rows, the follow-up is a
    refresh bounded to those rows' slots through a row-to-slot inverse
    on the plan.

    Differentiable under every impl — the custom VJP
    re-derives the gradient through the *un-elided* XLA oracle math, so
    SPAC stays forward-only (DESIGN.md §2).

    With ``epilogue`` (inference-only) the fused BN/ReLU epilogue runs on
    each finished output block and the return value becomes
    ``(out, ActSparsity)`` for the next layer; ``bias`` must then be None
    (fold it into the epilogue shift).

    Dispatch is guarded (runtime/guard.py, DESIGN.md §11): the resolved
    impl is retried once (a transient/injected fault recovers with the
    same impl), then quarantined per shape class and served by the XLA
    oracle 'ref'. ``REPRO_GUARD_FALLBACK=0`` disables the chain.
    """
    from repro.runtime import fault as _fault, guard as _guard
    impl = impl or kernel_impl()
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown kernel impl {impl!r}")
    if epilogue is not None and bias is not None:
        raise ValueError("bias and epilogue together would apply the bias "
                         "twice: fold it into the epilogue shift "
                         "(spconv.fold_bn_inference)")
    bm, bo = tiles.bm, tiles.bo
    c_in = feats.shape[1]
    c_out = weights.shape[-1]
    w = _pad_cout(weights, bn)
    c_out_pad = w.shape[-1]
    bk_ = bk if bk is not None else pick_bk(c_in, bm=bm, bn=bn, bo=bo,
                                            c_out=c_out_pad)
    if c_in % bk_ != 0:
        raise ValueError(f"bk={bk_} must divide Cin={c_in}")
    n_k = c_in // bk_

    if row_nz is None and act is not None:
        row_nz = act.row_nz
    tile_nz_geo = tiles.tile_nz
    if row_nz is None:
        tile_nz = tile_nz_geo
        tile_bk_nz = jnp.repeat(tile_nz[:, None], n_k, axis=1)
    else:
        blk_nz = None
        if n_k > 1 and spac_block_enabled():
            if act is not None:
                blk_nz = act.block_liveness(c_in, bk_)
            if blk_nz is None:
                blk_nz = _sparsity.row_block_nonzero(feats, bk_)
            # keep block liveness consistent with the (possibly coarser)
            # row mask: a live block must never outlive its tile
            blk_nz = blk_nz & row_nz[:, None]
        tile_nz, tile_bk_nz = refresh_liveness(tiles, row_nz, blk_nz,
                                               n_k=n_k, src_valid=src_valid)
    n_out_pad = -(-n_out // bo) * bo

    if epilogue is not None:
        scale = jnp.pad(epilogue.scale.astype(jnp.float32),
                        (0, c_out_pad - c_out))
        shift = jnp.pad(epilogue.shift.astype(jnp.float32),
                        (0, c_out_pad - c_out))
        valid_pad = jnp.pad(epilogue.valid.astype(jnp.int32),
                            (0, n_out_pad - n_out))

    def _run(one: str):
        _fault.check("gemm")
        cfg = (n_out, n_out_pad, bm, bn, bo, bk_, one)
        if epilogue is not None:
            return _exec_fused_epi(cfg, feats, w, scale, shift, valid_pad,
                                   tiles.gather_idx, tiles.tile_tap,
                                   tile_nz, tile_bk_nz, tiles.scatter_idx,
                                   tiles.tile_ob, tiles.tile_first,
                                   tiles.tile_run, tiles.grp_skip,
                                   tiles.grp_contig)
        return _exec_fused(cfg, feats, w, tiles.gather_idx, tiles.tile_tap,
                           tile_nz, tile_bk_nz, tile_nz_geo,
                           tiles.scatter_idx, tiles.tile_ob,
                           tiles.tile_first, tiles.tile_run, tiles.grp_skip,
                           tiles.grp_contig)

    chain = _guard.FALLBACK_CHAINS["gemm"].get(impl, ())
    res = _guard.dispatch("gemm", impl, chain, _run,
                          key=(tuple(feats.shape), w.shape[-1], bm, bo))
    if epilogue is not None:
        out, nz = res
        nzb = nz.astype(bool)
        return out[:, :c_out], _sparsity.ActSparsity(
            row_nz=nzb.any(-1), blk_nz=nzb, blk=bn)
    out = res[:, :c_out]
    if bias is not None:
        out = out + bias
    return out


def apply_kmap_fused(feats: jnp.ndarray, weights: jnp.ndarray,
                     kmap: jnp.ndarray, bias: jnp.ndarray | None = None, *,
                     spac: bool = True, bm: int = 128, bn: int = 128,
                     bo: int | None = None, bk: int | None = None,
                     impl: str | None = None) -> jnp.ndarray:
    """One-shot fused path: build geometry tiles and execute without
    materializing the gathered lhs. SPAC liveness rides as a per-layer
    refresh (``row_nz``), never folded into the build: build-time elision
    would re-pack the tap segments (different summation order — no longer
    bit-identical to spac=False) and bake the feature-dependent mask into
    the gather stream where the backward could not undo it (DESIGN.md §2).
    """
    impl = impl or kernel_impl()
    row_nz = _sparsity.row_nonzero(feats) if spac else None
    tiles = build_tap_tiles(kmap, None, bm=bm, bo=bo)
    return apply_tiles(feats, weights, tiles, bias, n_out=kmap.shape[0],
                       row_nz=row_nz, bn=bn, bk=bk, impl=impl)


def apply_kmap(feats: jnp.ndarray, weights: jnp.ndarray, kmap: jnp.ndarray,
               bias: jnp.ndarray | None = None, *, spac: bool = True,
               bm: int = 128, bn: int = 128, bo: int | None = None,
               impl: str | None = None) -> jnp.ndarray:
    """Materialized-gather baseline: semantically identical to
    rulebook.apply_kmap_gather (tested), but pays an (M_pad, Cin) HBM
    intermediate for the gather, an (M_pad, Cout) partial-product array,
    and a post-kernel XLA scatter-add. Kept as the comparison point for
    benchmarks/rulebook_exec.py; the default backend is the fused path."""
    impl = impl or kernel_impl()
    if bo is None:
        bo = max(bm, 512)
    return _apply_kmap_materialized(feats, weights, kmap, bias, spac=spac,
                                    bm=bm, bn=bn, bo=bo, impl=impl)


@functools.partial(jax.jit,
                   static_argnames=("spac", "bm", "bn", "bo", "impl"))
def _apply_kmap_materialized(feats, weights, kmap, bias=None, *, spac, bm,
                             bn, bo, impl):
    n_out = kmap.shape[0]
    row_nz = _sparsity.row_nonzero(feats) if spac else None
    tiles = build_tap_tiles(kmap, row_nz, bm=bm, bo=bo)
    lhs = jnp.take(feats, tiles.gather_idx, axis=0)
    lhs = jnp.where(tiles.slot_valid[:, None], lhs, 0)
    c_out = weights.shape[-1]
    w = _pad_cout(weights, bn)
    if impl == "pallas":
        ps = spconv_gemm(lhs, w, tiles.tile_tap, tiles.tile_nz, bm=bm, bn=bn)
    elif impl == "interpret":
        ps = spconv_gemm(lhs, w, tiles.tile_tap, tiles.tile_nz, bm=bm, bn=bn,
                         interpret=True)
    else:
        ps = spconv_gemm_ref(lhs, w, tiles.tile_tap, tiles.tile_nz,
                             bm=bm, bn=bn)
    out = jnp.zeros((n_out + 1, w.shape[-1]), ps.dtype)
    out = out.at[tiles.scatter_idx].add(ps, mode="drop")[:n_out, :c_out]
    if bias is not None:
        out = out + bias
    return out
