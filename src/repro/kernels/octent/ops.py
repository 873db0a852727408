"""OCTENT engine ops: sort-free table build + impl-dispatched fused query.

This is the map-search sibling of kernels/spconv_gemm/ops.py: the plan
layer (core/plan.py) calls :func:`build_kmap` and gets whichever backend
fits the host —

  * ``pallas``    — compiled fused query kernel (TPU).
  * ``interpret`` — same kernel under the Pallas interpreter (CI/CPU).
  * ``ref``       — pure-XLA bit-level oracle of the same math (ref.py);
    the default off-TPU backend.
  * ``xla``       — the original dense-table builder
    (mapsearch.build_kmap_octree), retained as the PR-1-style oracle.

All backends return bit-identical kmaps (tested against the host hash
probe of [9]).

Stage 1 (:func:`build_query_table`) builds the octree directory + the
*compacted* banked table with zero XLA ``sort`` ops: block keys and flat
table addresses are bounded composites, so Morton-radix counting passes
(core/binning.py) reproduce the stable order the old global argsorts
produced. ``n_blocks`` reports the true occupied-block count — callers
must check it against ``max_blocks`` (plan.subm3_plan raises/flags; the
dense XLA builder silently dropped overflowing voxels before PR 3).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import binning, mapsearch, morton
from repro.kernels.octent.kernel import LANE, octent_query
from repro.kernels.octent.ref import octent_query_ref


def search_impl() -> str:
    """pallas | interpret | ref | xla | sharded — resolved per call site
    from ``REPRO_SEARCH_IMPL`` (documented in runtime/flags.py).

    Resolve *outside* jit boundaries and cache keys (core/plan.py does):
    the env var must be re-read per call, not frozen into a trace. When
    the active mesh splits the block-key axes (data/model) more than
    one way, ``auto`` resolves to the mesh-partitioned engine
    (kernels/octent/sharded.py) so models simply pick it up by running
    under the mesh.
    """
    impl = os.environ.get("REPRO_SEARCH_IMPL", "auto")
    if impl == "auto":
        from repro.runtime import sharding
        if sharding.blockkey_shards() > 1:
            return "sharded"
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def hardware_impl() -> str:
    """The impl that exercises the Pallas query kernel on this host: the
    compiled kernel on TPU, the interpreter elsewhere (tests/CI)."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


#: stage-2 query rows submitted since the last reset (trace-time count):
#: a full :func:`build_kmap` adds its N voxel rows, an ``update=`` call
#: adds only its (padded) dirty-row budget — the streaming parity
#: benchmarks compare exactly this number against the from-scratch cost
#: (DESIGN.md §15). Counted once per call, not per fallback retry.
QUERY_ROWS = [0]


def query_row_count() -> int:
    """Stage-2 query rows submitted since the last reset."""
    return QUERY_ROWS[0]


def reset_query_row_counter() -> None:
    QUERY_ROWS[0] = 0


class KmapUpdate(NamedTuple):
    """Incremental re-search request for :func:`build_kmap` (DESIGN.md §15).

    ``kmap`` is the previous frame's (N, K) kernel map over the *same*
    canonical slot layout as the coordinate stream being searched;
    ``rows`` the -1-padded (Q,) int32 slot indices whose 27-neighborhood
    touches a dirty block (core/stream.py computes them). Only those rows
    are re-queried against the (already delta-updated) table and
    scattered back; every other row's kmap entries are reused verbatim.
    """

    kmap: jnp.ndarray   # (N, K) int32 previous kernel map
    rows: jnp.ndarray   # (Q,) int32 rows to re-search, -1 padded


class QueryTable(NamedTuple):
    """Sort-free OCTENT search structure (kernel.py module doc).

    ``ublocks`` is the sorted block directory (INVALID padded); ``tkey`` /
    ``tval`` the compacted banked table: sorted flat addresses
    ``rank * 4096 + bank * 512 + row`` (LANE-padded with the out-of-range
    sentinel ``max_blocks * 4096``) and the voxel index per slot (-1 pad).
    ``n_blocks`` is the *true* occupied-block count — it may exceed
    ``max_blocks``, which is the caller's overflow signal.
    """

    ublocks: jnp.ndarray   # (max_blocks,) int32
    n_blocks: jnp.ndarray  # () int32
    tkey: jnp.ndarray      # (n_pad,) int32, sorted
    tval: jnp.ndarray      # (n_pad,) int32


@functools.partial(jax.jit, static_argnames=("max_blocks", "grid_bits",
                                             "batch_bits", "binning_mode"))
@jax.named_scope("plan.search")
def build_query_table(coords: jnp.ndarray, batch: jnp.ndarray,
                      valid: jnp.ndarray, *, max_blocks: int,
                      grid_bits: int = 7, batch_bits: int = 4,
                      binning_mode: str = "counting") -> QueryTable:
    """Stage 1: sort-free octree directory + compacted banked table.

    Args:
      coords: (N, 3) int32 voxel coordinates (padded rows allowed).
      batch:  (N,) int32 batch index per voxel.
      valid:  (N,) bool row-validity mask; invalid rows never enter the
        directory or the table.
      max_blocks: directory capacity (static). The flat table address
        space is ``max_blocks * 4096``, which must fit int32 (asserted).
      grid_bits, batch_bits: block-key bit budget (morton.block_key).
      binning_mode: 'counting' (Morton-radix passes, zero XLA sorts —
        the default and the audited path) | 'argsort' (retained global-
        sort baseline; bit-identical output).

    Returns:
      A :class:`QueryTable`. Invariants: ``ublocks`` is sorted ascending
      with INVALID padding; ``tkey`` is sorted ascending with the
      out-of-range sentinel ``max_blocks * 4096`` padding to a LANE
      multiple; ``tval[i] == -1`` iff slot i is padding; ``n_blocks`` is
      the *true* occupied-block count and may exceed ``max_blocks`` —
      the caller's overflow signal (plan.subm3_plan raises/flags).

    The result is geometry-only and safe to share: core/plan.py pins it
    in the content-keyed PinnedStore (DESIGN.md §10) so layers and
    training steps that replay the same coordinate set skip this build.
    """
    n = coords.shape[0]
    sentinel = max_blocks * morton.TABLE_SIZE
    assert sentinel < 2 ** 31, (
        f"max_blocks={max_blocks}: compacted table addresses overflow int32")
    bkey = jnp.where(valid,
                     morton.block_key(coords, batch, grid_bits, batch_bits),
                     mapsearch.INVALID)
    ublocks, n_blocks, rank = mapsearch.sorted_unique(
        bkey, max_blocks, nbits=3 * grid_bits + batch_bits,
        binning_mode=binning_mode)
    bank, row = morton.bank_and_row(morton.local_code(coords))
    tk = rank * morton.TABLE_SIZE + bank * morton.BANK_ROWS + row
    tk = jnp.where(valid & (rank < max_blocks), tk, sentinel)
    if binning_mode == "counting":
        order = binning.counting_argsort(tk, sentinel.bit_length())
    else:
        order = jnp.argsort(tk).astype(jnp.int32)
    tkey = tk[order]
    tval = jnp.where(tkey < sentinel, order, -1)
    pad = -(-n // LANE) * LANE - n
    tkey = jnp.pad(tkey, (0, pad), constant_values=sentinel)
    tval = jnp.pad(tval, (0, pad), constant_values=-1)
    return QueryTable(ublocks, n_blocks.astype(jnp.int32), tkey, tval)


@functools.partial(jax.jit, static_argnames=("bq",))
@jax.named_scope("plan.search")
def _pack_queries(coords, batch, valid, *, bq: int) -> jnp.ndarray:
    """Pack the voxel stream as (5, N_pad) int32 rows x/y/z/batch/valid."""
    n = coords.shape[0]
    n_pad = -(-n // bq) * bq
    q = jnp.zeros((5, n_pad), jnp.int32)
    q = q.at[0:3, :n].set(coords.T.astype(jnp.int32))
    q = q.at[3, :n].set(batch.astype(jnp.int32))
    return q.at[4, :n].set(valid.astype(jnp.int32))


def build_kmap(coords: jnp.ndarray, batch: jnp.ndarray, valid: jnp.ndarray,
               *, max_blocks: int, grid_bits: int = 7, batch_bits: int = 4,
               impl: str | None = None, bq: int = 128,
               offsets: jnp.ndarray | None = None,
               binning_mode: str = "counting",
               table: QueryTable | None = None,
               update: KmapUpdate | None = None
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Submanifold OCTENT map search: the full stage-1 + stage-2 engine.

    Args:
      coords, batch, valid: the padded coordinate stream (see
        :func:`build_query_table`).
      max_blocks: octree directory capacity (static).
      grid_bits, batch_bits: block-key bit budget.
      impl: pallas | interpret | ref | xla | sharded; None resolves via
        :func:`search_impl` (env flag ``REPRO_SEARCH_IMPL``, see
        runtime/flags.py). 'sharded' partitions the table by block-key
        range over the active mesh (kernels/octent/sharded.py) — bit-
        identical kmap. 'xla' is the retained dense-table builder.
      bq: query-tile height of the Pallas kernel grid.
      offsets: (K, 3) int32 kernel offsets (default: the 27 Subm3 taps).
      binning_mode: 'argsort' swaps the stage-1 radix passes for the
        retained global sorts (benchmark baseline; same kmap either way).
      table: a prebuilt stage-1 :class:`QueryTable` for this exact
        coordinate set and (max_blocks, grid_bits, batch_bits) — e.g.
        one pinned by core/plan.py (DESIGN.md §10) — so only the query
        runs. Accepted by the table-backed impls (pallas / interpret /
        ref) only; 'xla' and 'sharded' build their own structures and
        raise if one is passed.
      update: a :class:`KmapUpdate` carrying the previous frame's kmap
        and the -1-padded dirty-row indices (DESIGN.md §15): only those
        rows are re-queried against ``table`` and scattered into a copy
        of the previous kmap — untouched rows are reused bit-verbatim.
        Requires ``table`` (the structure must already reflect the new
        frame; this function never splices it) and therefore a
        table-backed impl. Rows listed with ``valid[row] == False``
        (evicted slots) re-resolve to all -1, matching a from-scratch
        build over the same arrays.

    Returns:
      ``(kmap, n_blocks)``: kmap (N, K) int32 with -1 misses, exactly as
      the oracles; ``n_blocks`` the true occupied-block count for the
      caller's overflow check (> max_blocks means voxels would have been
      dropped — plan.subm3_plan raises eagerly / flags under jit).

    Dispatch is guarded (runtime/guard.py, DESIGN.md §11): the resolved
    impl is retried once on failure (an injected one-shot fault or a
    flaky lowering recovers with the *same* impl — bit-identical
    output), then quarantined per shape class and served by its
    bit-exact fallback ('ref'). ``REPRO_GUARD_FALLBACK=0`` restores
    raw first-error propagation.
    """
    from repro.runtime import fault as _fault, guard as _guard
    impl = impl or search_impl()
    if impl not in ("pallas", "interpret", "ref", "xla", "sharded"):
        raise ValueError(f"unknown search impl {impl!r}")
    if offsets is None:
        offsets = jnp.asarray(morton.subm3_offsets())
    if table is not None and impl not in ("pallas", "interpret", "ref"):
        raise ValueError(
            f"impl={impl!r} builds its own search structure; a prebuilt "
            f"QueryTable is only consumed by the table-backed impls "
            f"(pallas | interpret | ref)")
    if update is not None and table is None:
        raise ValueError(
            "update= re-searches dirty rows against a delta-updated "
            "QueryTable and never builds one itself: pass the table= the "
            "stream spliced for this frame (core/stream.py does)")
    QUERY_ROWS[0] += (update.rows.shape[0] if update is not None
                      else coords.shape[0])
    if impl == "sharded":
        # configuration errors (no usable mesh) must surface to the
        # caller, not be served by the fallback chain
        from repro.kernels.octent import sharded
        sharded.require_blockkey_mesh()

    def _run(one: str):
        _fault.check("search")
        if one == "sharded":
            from repro.kernels.octent import sharded
            return sharded.build_kmap_sharded(
                coords, batch, valid, max_blocks=max_blocks,
                grid_bits=grid_bits, batch_bits=batch_bits, offsets=offsets,
                binning_mode=binning_mode)
        if one == "xla":
            bt = mapsearch.build_block_table(
                coords, batch, valid, max_blocks=max_blocks,
                grid_bits=grid_bits, batch_bits=batch_bits,
                binning_mode=binning_mode)
            q = coords[:, None, :] + offsets[None, :, :]
            qb = jnp.broadcast_to(batch[:, None], q.shape[:2])
            qv = jnp.broadcast_to(valid[:, None], q.shape[:2])
            kmap = mapsearch.query_block_table(bt, q, qb, qv,
                                               grid_bits=grid_bits,
                                               batch_bits=batch_bits)
            return kmap, bt.n_blocks.astype(jnp.int32)
        # a table prebuilt for the primary is reusable by any table-backed
        # fallback — it depends only on geometry, not the query impl
        qt = table if table is not None else build_query_table(
            coords, batch, valid, max_blocks=max_blocks,
            grid_bits=grid_bits, batch_bits=batch_bits,
            binning_mode=binning_mode)
        if update is not None:
            # delta path: query only the dirty rows, splice into the
            # previous kmap. The row gather/scatter (not the query math)
            # is what differs from the full path, so any table-backed
            # fallback stays bit-identical.
            rows = update.rows
            sel = jnp.where(rows >= 0, rows, 0)
            qc, qb2 = coords[sel], batch[sel]
            qv = valid[sel] & (rows >= 0)
            if one == "ref":
                sub = octent_query_ref(qc, qb2, qv, offsets,
                                       qt.ublocks, qt.tkey, qt.tval,
                                       qt.n_blocks, grid_bits=grid_bits,
                                       batch_bits=batch_bits)
            else:
                qpack = _pack_queries(qc, qb2, qv, bq=bq)
                out = octent_query(qpack, offsets.astype(jnp.int32),
                                   qt.ublocks, qt.tkey, qt.tval,
                                   qt.n_blocks, grid_bits=grid_bits,
                                   batch_bits=batch_bits, bq=bq,
                                   interpret=one == "interpret")
                sub = out[:, :rows.shape[0]].T
            safe = jnp.where(rows >= 0, rows, coords.shape[0])
            kmap = update.kmap.at[safe].set(sub, mode="drop")
            return kmap, qt.n_blocks
        if one == "ref":
            kmap = octent_query_ref(coords, batch, valid, offsets,
                                    qt.ublocks, qt.tkey, qt.tval,
                                    qt.n_blocks, grid_bits=grid_bits,
                                    batch_bits=batch_bits)
        else:
            n = coords.shape[0]
            qpack = _pack_queries(coords, batch, valid, bq=bq)
            out = octent_query(qpack, offsets.astype(jnp.int32), qt.ublocks,
                               qt.tkey, qt.tval, qt.n_blocks,
                               grid_bits=grid_bits, batch_bits=batch_bits,
                               bq=bq, interpret=one == "interpret")
            kmap = out[:, :n].T
        return kmap, qt.n_blocks

    chain = _guard.FALLBACK_CHAINS["search"].get(impl, ())
    return _guard.dispatch(
        "search", impl, chain, _run,
        key=(coords.shape[0], offsets.shape[0], max_blocks,
             grid_bits, batch_bits,
             update.rows.shape[0] if update is not None else None))
