"""Pallas TPU kernels: tap-grouped (ragged) gather-GEMM for SpConv.

The SPAC pipeline of paper §V (gather / MAC / arrangement stages overlapped,
output-stationary partial sums on chip) mapped onto the MXU:

  * the 16x16 MAC array becomes (bm x bk) @ (bk x bn) MXU tiles;
  * the rulebook is pre-sorted output-block-major, tap-minor (hottest tap
    first within each block) and padded so every m-tile is single-tap and
    single-output-block; ``tile_tap`` (scalar-prefetched) drives the
    *weight* BlockSpec index_map so consecutive tiles of the same tap reuse
    the VMEM-resident weight block, and ``tile_ob`` drives the *output*
    BlockSpec so a run of tiles targeting the same output block accumulates
    into one VMEM-resident output block (the Ofmap Arranger, §V-A).
  * ``tile_nz`` marks tiles that are all padding or whose gathered rows are
    all zero (post-ReLU): compute AND row DMAs are skipped via @pl.when —
    the SPAC elision at tile grain.

Two entry points (DESIGN.md §6):

  * :func:`spconv_gemm`       — takes a pre-gathered, bm-padded lhs and
    returns (M_pad, Cout) partial products for an external scatter-add.
    The original materialized baseline.
  * :func:`spconv_gemm_fused` — the default execution backend
    (core/plan.py). Takes the *full* feature array plus gather indices
    (streamed per tile into SMEM) and per-tile run metadata; rows are
    pulled straight out of HBM by double-buffered DMAs (tile r+1's copies
    fly while tile r computes), C_in is processed in bk-sized blocks with
    an f32 VMEM accumulator, and partial sums are scatter-added *inside the kernel*
    into the output block — neither the (M_pad, C_in) gathered copy nor
    the (M_pad, C_out) partial-product array ever exists in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Contiguity metadata granularity: gather runs are detected per GRP-slot
# group at plan-build time (ops.build_tap_tiles); a contiguous group is one
# strided DMA instead of GRP per-row DMAs, and a whole-tile run is a single
# bm-row DMA. Must divide bm (ops asserts); bm/GRP <= 32 so the per-tile
# masks fit int32.
GRP = 8

#: lane width: feature rows are DMA'd in whole 128-lane tiles
LANE = 128

#: MXU precision of the fused kernel's two dots. Float32 operands are
#: contracted at fp32 (HIGHEST: multi-pass bf16 on the MXU), so the
#: in-kernel one-hot scatter moves the f32 partial sums without rounding
#: them to bf16 and the layer matches the f32 oracle (DESIGN.md §6).
F32 = jax.lax.Precision.HIGHEST


def _kernel(tile_tap_ref, tile_nz_ref, lhs_ref, w_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(tile_nz_ref[i] != 0)
    def _compute():
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)

    @pl.when(tile_nz_ref[i] == 0)
    def _skip():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def spconv_gemm(lhs: jnp.ndarray, weights: jnp.ndarray,
                tile_tap: jnp.ndarray, tile_nz: jnp.ndarray,
                *, bm: int = 128, bn: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """lhs (M, Cin) pre-gathered rows (tile-sorted, bm-padded); weights
    (K, Cin, Cout); tile_tap/tile_nz (M/bm,). Returns (M, Cout) partial
    products, one row per map, ready for the scatter-add."""
    m, c_in = lhs.shape
    k, _, c_out = weights.shape
    assert m % bm == 0 and c_out % bn == 0, (m, bm, c_out, bn)
    n_m, n_n = m // bm, c_out // bn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_m, n_n),
        in_specs=[
            pl.BlockSpec((bm, c_in), lambda i, j, tap, nz: (i, 0)),
            # weight block chosen by the prefetched tap id: same tap on the
            # next tile => same block index => Mosaic keeps it VMEM-resident
            pl.BlockSpec((1, c_in, bn), lambda i, j, tap, nz: (tap[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, tap, nz: (i, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, c_out), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="spconv_gemm",
    )(tile_tap, tile_nz, lhs, weights)


def _row_dmas(do, gidx_ref, run, skip, contig, feats_ref, rows_ref, sem,
              k2, slot, *, bm, grp):
    """Start or wait the gather DMAs of one tile's Cin-block ``k2`` into
    buffer ``slot``. ``gidx_ref`` is that tile's (1, 1, bm) SMEM block of
    gather indices; ``run`` / ``skip`` / ``contig`` its run metadata. The
    wait path mirrors the start path exactly (same descriptors on the same
    semaphore), so starts and waits always balance.

    Copy granularity is chosen from the plan-build run metadata: a
    whole-tile run is one bm-row strided copy; a contiguous GRP-slot group
    is one GRP-row copy; everything else falls back to per-row copies.
    Groups with no valid slot are skipped entirely — their (garbage) rows
    are dropped by the in-kernel scatter, so they cost no bandwidth at all.
    """
    def cp(nrows, src_row, dst_row):
        c = pltpu.make_async_copy(
            feats_ref.at[k2, pl.ds(src_row, nrows)],
            rows_ref.at[slot, pl.ds(dst_row, nrows)],
            sem.at[slot])
        c.start() if do == "start" else c.wait()

    @pl.when(run)
    def _whole_tile():
        cp(bm, gidx_ref[0, 0, 0], 0)

    @pl.when(~run)
    def _grouped():
        for g in range(bm // grp):
            live = ((skip >> g) & 1) == 0
            is_contig = ((contig >> g) & 1) != 0

            @pl.when(live & is_contig)
            def _one_copy(g=g):
                cp(grp, gidx_ref[0, 0, g * grp], g * grp)

            @pl.when(live & ~is_contig)
            def _per_row(g=g):
                for r in range(grp):
                    cp(1, gidx_ref[0, 0, g * grp + r], g * grp + r)


def _os_kernel(tile_tap_ref, tile_nz_ref, tile_bk_ref, tile_ob_ref,
               tile_first_ref, tile_last_ref, tile_run_ref, grp_skip_ref,
               grp_contig_ref, gcur_ref, gnxt_ref, scat_row_ref,
               scat_col_ref, feats_ref, w_ref, *rest,
               bm: int, bn: int, bo: int, grp: int, epilogue: bool):
    if epilogue:
        (scale_ref, shift_ref, valid_ref, out_ref, nz_ref,
         rows_ref, acc_ref, sem) = rest
    else:
        out_ref, rows_ref, acc_ref, sem = rest
    i = pl.program_id(0)
    k = pl.program_id(1)
    j = pl.program_id(2)
    n_m = pl.num_programs(0)
    n_k = pl.num_programs(1)
    n_n = pl.num_programs(2)
    bk = rows_ref.shape[-1]
    s = i * n_k + k                   # DMA step: one rows-block per (i, k)
    slot = s % 2

    def dmas(do, gidx_ref, i2, k2, slot):
        _row_dmas(do, gidx_ref, tile_run_ref[i2] != 0, grp_skip_ref[i2],
                  grp_contig_ref[i2], feats_ref, rows_ref, sem, k2, slot,
                  bm=bm, grp=grp)

    nz = tile_nz_ref[i] != 0
    # Cin-block grain SPAC (DESIGN.md §14): a dead (tile, Cin-block) pair —
    # every gathered row's bk-slice is exactly zero — costs neither the
    # gather DMA nor the MAC. tile_bk_ref[i * n_k + k] <= tile_nz_ref[i] by
    # construction (ops.tile_block_liveness), so a live block implies a
    # live tile.
    blk = tile_bk_ref[s] != 0

    # -- gather stage, double-buffered: step s+1's copies are started before
    # step s's compute, so the next tile/Cin-block fetch overlaps the MACs.
    # The gather indices of this tile and the next arrive as pipelined SMEM
    # blocks (gcur / gnxt), never as one whole-array prefetch. Dead blocks
    # start no copies and wait on none; slot parity stays consistent
    # because start and wait are gated by the same tile_bk entry.
    @pl.when(j == 0)
    def _dma_schedule():
        @pl.when((s == 0) & blk)
        def _warmup():
            dmas("start", gcur_ref, i, k, slot)

        s1 = jnp.minimum(s + 1, n_m * n_k - 1)
        more = s + 1 < n_m * n_k
        same_tile = k + 1 < n_k

        @pl.when(more & same_tile & (tile_bk_ref[s1] != 0))
        def _prefetch_next_block():
            dmas("start", gcur_ref, i, k + 1, 1 - slot)

        @pl.when(more & ~same_tile & (tile_bk_ref[s1] != 0))
        def _prefetch_next_tile():
            dmas("start", gnxt_ref, jnp.minimum(i + 1, n_m - 1), 0,
                 1 - slot)

        @pl.when(blk)
        def _arrived():
            dmas("wait", gcur_ref, i, k, slot)

    # -- MAC stage: (bm, bk) @ (bk, bn) MXU tiles, f32 accumulation over the
    # Cin blocks in a VMEM scratch (never written back to HBM). A live tile
    # whose k==0 block is dead still zero-initializes the accumulator slice
    # (the skipped rows buffer holds garbage from an earlier tile — it must
    # never be read, and the later live blocks need a clean base to add to).
    @pl.when(blk)
    def _compute():
        partial = jax.lax.dot_general(
            rows_ref[slot], w_ref[0],
            (((1,), (0,)), ((), ())), precision=F32,
            preferred_element_type=jnp.float32)

        @pl.when(k == 0)
        def _init():
            acc_ref[:, pl.ds(j * bn, bn)] = partial

        @pl.when(k > 0)
        def _accum():
            acc_ref[:, pl.ds(j * bn, bn)] += partial

    @pl.when(nz & ~blk & (k == 0))
    def _init_dead_block():
        acc_ref[:, pl.ds(j * bn, bn)] = jnp.zeros((bm, bn), jnp.float32)

    # -- arrangement stage: once per tile (at its last grid step), scatter
    # the accumulated (bm, Cout) partial sums into the output block that
    # owns this tile. Consecutive tiles of the same output block revisit
    # the same out_ref index, so the block stays VMEM-resident for the
    # whole run and is written back to HBM exactly once — the (M_pad, Cout)
    # partial-product array never exists.
    @pl.when((k == n_k - 1) & (j == n_n - 1))
    def _arrange():
        first = tile_first_ref[i] != 0

        @pl.when(first & ~nz)
        def _open_empty():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(nz)
        def _scatter():
            # local row of each slot inside this output block; slots whose
            # target lies outside (padding and SPAC-elided maps) select no
            # row of the one-hot matrix and are masked before the matmul so
            # uninitialized gather rows can never poison the output. The
            # targets arrive twice, as a (1, bm) row for the one-hot matrix
            # and as a (bm, 1) column for the row mask: Mosaic does not
            # relayout a lane vector into a sublane one.
            base = tile_ob_ref[i] * bo
            local = scat_row_ref[0] - base
            inb = (local >= 0) & (local < bo)
            sel = (jax.lax.broadcasted_iota(jnp.int32, (bo, bm), 0)
                   == local) & inb
            local_c = scat_col_ref[...] - base
            inb_c = (local_c >= 0) & (local_c < bo)
            contrib = jax.lax.dot_general(
                sel.astype(jnp.float32),
                jnp.where(inb_c, acc_ref[...], 0.0),
                (((1,), (0,)), ((), ())), precision=F32,
                preferred_element_type=jnp.float32).astype(out_ref.dtype)

            @pl.when(first)
            def _open():
                out_ref[...] = contrib

            @pl.when(~first)
            def _add():
                out_ref[...] += contrib

        # -- fused epilogue (DESIGN.md §14): when the closing tile of an
        # output block's run lands, the finished block is still
        # VMEM-resident — apply BN-inference scale/shift + ReLU in place
        # and record the per-(row, bn-group) zero pattern, so the next
        # layer's SPAC liveness refresh never re-sweeps the features in
        # HBM. Runs for empty blocks too (shift can resurrect zero rows);
        # invalid rows (block padding past n_out, masked-off voxels) are
        # forced to zero so they stay dead in the emitted masks.
        if epilogue:
            @pl.when(tile_last_ref[i] != 0)
            def _bn_relu():
                y = (out_ref[...].astype(jnp.float32) * scale_ref[...]
                     + shift_ref[...])
                y = jnp.where(valid_ref[...] != 0, jnp.maximum(y, 0.0), 0.0)
                yc = y.astype(out_ref.dtype)
                out_ref[...] = yc
                for g in range(nz_ref.shape[-1]):
                    nz_ref[:, g:g + 1] = jnp.max(
                        (yc[:, g * bn:(g + 1) * bn] != 0).astype(jnp.int32),
                        axis=1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bo", "bk", "n_out_pad",
                              "epilogue", "interpret"))
def spconv_gemm_fused(feats: jnp.ndarray, weights: jnp.ndarray,
                      gather_idx: jnp.ndarray, scatter_idx: jnp.ndarray,
                      tile_tap: jnp.ndarray, tile_nz: jnp.ndarray,
                      tile_ob: jnp.ndarray, tile_first: jnp.ndarray,
                      tile_run: jnp.ndarray, grp_skip: jnp.ndarray,
                      grp_contig: jnp.ndarray,
                      tile_bk_nz: jnp.ndarray | None = None,
                      tile_last: jnp.ndarray | None = None,
                      epi_scale: jnp.ndarray | None = None,
                      epi_shift: jnp.ndarray | None = None,
                      epi_valid: jnp.ndarray | None = None, *, bm: int = 128,
                      bn: int = 128, bo: int = 128, bk: int | None = None,
                      n_out_pad: int, epilogue: bool = False,
                      interpret: bool = False):
    """Output-stationary gather-fused rulebook GEMM (DESIGN.md §6, §14).

    feats (N, Cin) stays whole in HBM; gather_idx (M_pad,) maps each slot to
    its source row; scatter_idx (M_pad,) maps it to its output row, which by
    the ops.build_tap_tiles layout contract falls inside the bo-row output
    block ``tile_ob[t]`` of its tile (or outside every block, for padding —
    those slots are dropped in-kernel). tile_first flags the opening tile of
    each output-block run; tile_run / grp_skip / grp_contig carry the
    plan-built gather-run metadata (whole-tile runs, per-GRP-group
    contiguity and liveness bitmasks). Returns the scattered (n_out_pad,
    Cout) output — no (M_pad, Cin) gather copy, no (M_pad, Cout) partials.

    ``tile_bk_nz`` (n_m, n_k) refines the tile skip to Cin-block grain
    (ops.tile_block_liveness); entries must never be live where the tile is
    dead. None falls back to tile grain. With ``epilogue=True`` the kernel
    additionally applies ``y = relu(out * epi_scale + epi_shift)`` masked by
    ``epi_valid`` to each finished output block in VMEM (``tile_last`` marks
    each block run's closing tile) and returns ``(out, nz)`` where nz
    (n_out_pad, Cout/bn) int32 is the next layer's per-(row, bn-group)
    liveness — emitted in-kernel, no HBM re-sweep (DESIGN.md §14).

    The kernel reads features as (n_k, N, bk) Cin-block slabs, one DMA per
    row run. Mosaic slices an HBM row at any offset only out of a slab one
    lane tile (LANE) wide, so a ``bk`` that is all of Cin or a LANE
    multiple is re-blocked to LANE here: Cin is zero-padded up to a LANE
    multiple (zero columns add exact zeros) and each ``tile_bk_nz`` column
    is repeated over its LANE sub-blocks. Any other ``bk`` is kept as it
    is, which only the interpreter accepts.
    """
    _, c_in = feats.shape
    k_taps, _, c_out = weights.shape
    m = gather_idx.shape[0]
    bk = c_in if bk is None else bk
    assert m % bm == 0 and c_out % bn == 0, (m, bm, c_out, bn)
    assert c_in % bk == 0, (c_in, bk)
    if tile_bk_nz is None:
        tile_bk_nz = jnp.repeat(tile_nz[:, None], c_in // bk, axis=1)
    if bk == c_in or bk % LANE == 0:
        pad = -c_in % LANE
        if pad:
            feats = jnp.pad(feats, ((0, 0), (0, pad)))
            weights = jnp.pad(weights, ((0, 0), (0, pad), (0, 0)))
        tile_bk_nz = jnp.repeat(tile_bk_nz, (bk + pad) // LANE, axis=1)
        c_in, bk = c_in + pad, LANE
    assert interpret or bk == LANE, (c_in, bk)
    assert n_out_pad % bo == 0, (n_out_pad, bo)
    grp = GRP if bm % GRP == 0 else bm
    assert bm // grp <= 32, (bm, grp)
    n_m, n_k, n_n = m // bm, c_in // bk, c_out // bn
    for t in (tile_tap, tile_nz, tile_ob, tile_first, tile_run, grp_skip,
              grp_contig):
        assert t.shape[0] == n_m, (t.shape, n_m)
    assert tile_bk_nz.shape == (n_m, n_k), (tile_bk_nz.shape, n_m, n_k)
    if tile_last is None:
        tile_last = jnp.concatenate(
            [(tile_ob[1:] != tile_ob[:-1]).astype(jnp.int32),
             jnp.ones(1, jnp.int32)])

    # index maps see the 9 scalar-prefetch refs appended; only tap/ob used
    ob_map = lambda i, k, j, tap, nz, bk_nz, ob, *pf: (ob[i], 0)
    smem_tile = lambda f: pl.BlockSpec((1, 1, bm), f,
                                       memory_space=pltpu.SMEM)
    in_specs = [
        # gather indices of this tile and of the next one, streamed as
        # per-tile SMEM blocks: the DMA descriptors read them as scalars
        smem_tile(lambda i, k, j, *pf: (i, 0, 0)),
        smem_tile(lambda i, k, j, *pf: (jnp.minimum(i + 1, n_m - 1), 0, 0)),
        # per-slot output targets of this tile, as a row and as a column
        pl.BlockSpec((1, 1, bm), lambda i, k, j, *pf: (i, 0, 0)),
        pl.BlockSpec((bm, 1), lambda i, k, j, *pf: (i, 0)),
        # full feature slabs, left in HBM: rows are DMA'd on demand
        pl.BlockSpec(memory_space=pltpu.HBM),
        # weight block chosen by the prefetched tap id and the Cin block
        pl.BlockSpec((1, bk, bn), lambda i, k, j, tap, *pf: (tap[i], k, j)),
    ]
    gidx = gather_idx.reshape(n_m, 1, bm)
    operands = [tile_tap, tile_nz, tile_bk_nz.reshape(-1), tile_ob,
                tile_first, tile_last, tile_run, grp_skip, grp_contig,
                gidx, gidx, scatter_idx.reshape(n_m, 1, bm),
                scatter_idx.reshape(m, 1),
                feats.reshape(-1, n_k, bk).transpose(1, 0, 2), weights]
    if epilogue:
        assert epi_scale is not None and epi_shift is not None \
            and epi_valid is not None
        in_specs += [
            pl.BlockSpec((1, c_out), lambda i, k, j, *pf: (0, 0)),
            pl.BlockSpec((1, c_out), lambda i, k, j, *pf: (0, 0)),
            pl.BlockSpec((bo, 1), ob_map),
        ]
        operands += [epi_scale.reshape(1, c_out).astype(jnp.float32),
                     epi_shift.reshape(1, c_out).astype(jnp.float32),
                     epi_valid.reshape(n_out_pad, 1).astype(jnp.int32)]
        out_specs = [pl.BlockSpec((bo, c_out), ob_map),
                     pl.BlockSpec((bo, n_n), ob_map)]
        out_shape = [jax.ShapeDtypeStruct((n_out_pad, c_out), feats.dtype),
                     jax.ShapeDtypeStruct((n_out_pad, n_n), jnp.int32)]
    else:
        out_specs = pl.BlockSpec((bo, c_out), ob_map)
        out_shape = jax.ShapeDtypeStruct((n_out_pad, c_out), feats.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(n_m, n_k, n_n),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, bm, bk), feats.dtype),
            pltpu.VMEM((bm, c_out), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_os_kernel, bm=bm, bn=bn, bo=bo, grp=grp,
                          epilogue=epilogue),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # rows / acc scratch and the output block are carried across grid
        # steps, so every dimension must execute in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="spconv_gemm_fused",
    )(*operands)
