"""Sharded OCTENT map search: the QueryTable over a device mesh.

The single-device engine (kernels/octent/ops.py) keeps the whole sorted
block directory (``ublocks``) and compacted banked voxel table
(``tkey``/``tval``) resident on one chip. This module partitions both by
**contiguous block-key range** across the mesh's data/model axes
(runtime.sharding.blockkey_axes) and runs the query under ``shard_map``:

  * directory — ``ublocks`` is already sorted by block Morton key, so S
    equal position-slices of it *are* S contiguous key ranges; shard s
    owns global block ranks [s*B, (s+1)*B). ``bounds[s]`` (the first key
    of slice s) is the boundary list: ownership of a query's block key is
    a single lower-bound against ``bounds``.
  * voxel table — ``tkey`` is sorted by the composite flat address
    ``rank * 4096 + bank * 512 + row`` (block-rank-major), so its S equal
    position-slices are contiguous *address* ranges aligned with the
    directory partition. Each device holds n_pad/S table slots — the full
    voxel table never materializes inside the mapped region, which is the
    jaxpr contract :func:`repro.core.binning.shard_body_avals_with_shape`
    audits.

Query routing is SPMD: every shard sees every query (27 per voxel,
generated exactly as the ref), answers only those whose key lands in its
slice (an exact match against a slice entry *is* the ownership test —
keys are unique across slices), and contributes ``-1`` elsewhere. At most
one shard can hit per query, so the per-shard partial kmaps merge with a
single ``lax.pmax`` — an associative integer reduce, hence bit-identical
to the single-device ``build_kmap`` on every mesh shape. (That
uniqueness rests on the COO contract every engine in this repo assumes:
no two valid voxels share (batch, coords). Duplicate rows give the
single-device oracles themselves divergent answers — the dense-table
builder overwrites one of them arbitrarily — so they are outside the
parity contract here too.) Two collectives
run per search: one pmax to publish the owner's global block rank (stage
1 -> stage 2 routing: the shard owning a block key is generally not the
shard owning the derived table address), one to merge the kmap.

The replicated stage-1 build (ops.build_query_table) is per-voxel
preprocessing, same class as the coordinate stream itself; only the
search *structure* it emits is distributed.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import get_abstract_mesh

from repro.core import mapsearch, morton
from repro.kernels.octent.kernel import LANE
from repro.kernels.octent.ref import encode_queries
from repro.runtime import sharding


class ShardedQueryTable(NamedTuple):
    """A QueryTable laid out as S contiguous block-key ranges.

    ``ublocks`` (S*B,) and ``tkey``/``tval`` (S*L,) carry the same sorted
    content as the single-device table, padded so every shard gets an
    equal slice (INVALID / table-sentinel / -1 padding preserves search
    semantics). ``bounds`` (S+1,) are the directory boundary keys —
    shard s owns block keys in [bounds[s], bounds[s+1]) — and ``tbounds``
    the same for the table's flat-address space (a block's voxels can
    straddle two table shards; lookups are exact-key, so only the
    boundary owner answers).
    """

    ublocks: jnp.ndarray   # (S*B,) int32, sorted, INVALID padded
    n_blocks: jnp.ndarray  # () int32 — true occupied-block count
    tkey: jnp.ndarray      # (S*L,) int32, sorted flat addresses
    tval: jnp.ndarray      # (S*L,) int32 voxel index per slot (-1 pad)
    bounds: jnp.ndarray    # (S+1,) int32 directory shard boundary keys
    tbounds: jnp.ndarray   # (S+1,) int32 table shard boundary addresses
    n_shards: int          # static S
    axes: tuple            # mesh axes the key range partitions over


def _pad_sorted(x: jnp.ndarray, size: int, fill) -> jnp.ndarray:
    return jnp.pad(x, (0, size - x.shape[0]), constant_values=fill)


def _pin(x: jnp.ndarray, mesh, spec: P) -> jnp.ndarray:
    """Lay ``x`` out sharded: constraint under trace, device_put eagerly.

    Off-trace placement needs a physical mesh: an abstract one is
    resolved to the concrete mesh of the context (sharding.concrete_mesh);
    without one the array stays where it is — shard_map's in_specs still
    distribute it at query time.
    """
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    mesh = sharding.concrete_mesh(mesh)
    if mesh is not None:
        return jax.device_put(x, NamedSharding(mesh, spec))
    return x


def _resolve_mesh(mesh, axes):
    mesh = mesh if mesh is not None else get_abstract_mesh()
    if mesh is None or mesh.empty:
        raise ValueError(
            "sharded OCTENT search needs an active device mesh — enter one "
            "with jax.set_mesh (or pass mesh=), or use "
            "a single-device impl ('ref'/'pallas'/'xla')")
    axes = tuple(axes) if axes is not None else sharding.blockkey_axes(mesh)
    if not axes:
        raise ValueError(
            f"mesh axes {tuple(mesh.axis_names)} contain none of the block-"
            f"key shard axes {sharding.SHARD_AXES}; the octree table has "
            f"nothing to partition over")
    return mesh, axes


def build_query_table_sharded(coords: jnp.ndarray, batch: jnp.ndarray,
                              valid: jnp.ndarray, *, max_blocks: int,
                              grid_bits: int = 7, batch_bits: int = 4,
                              binning_mode: str = "counting",
                              mesh=None, axes: tuple | None = None
                              ) -> ShardedQueryTable:
    """Stage 1 for the mesh: sort-free build + key-range layout.

    The directory pads to S equal block slices and the compacted table to
    S equal (LANE-aligned) slot slices; both are pinned to the mesh with
    the block-key PartitionSpec so each device stores only its range.

    Args:
      coords, batch, valid: the padded coordinate stream, exactly as
        ``ops.build_query_table``.
      max_blocks, grid_bits, batch_bits, binning_mode: forwarded to the
        (replicated) single-device stage-1 build.
      mesh: the device mesh (default: the active one; required — this
        impl has nothing to partition over without one).
      axes: mesh axes to partition the key range over (default:
        ``runtime.sharding.blockkey_axes`` — every data/model axis).

    Returns:
      A :class:`ShardedQueryTable` with S = prod(extent of ``axes``)
      contiguous key-range slices. Invariants: slice boundaries
      (``bounds``/``tbounds``) are the first key of each slice; padding
      (INVALID / address sentinel / -1) never matches a query;
      ``n_blocks`` is shard-uniform (replicated build), so the overflow
      check needs no collective.

    Unlike the single-device QueryTable, this structure is laid out for
    one specific mesh and is *not* pinned in the content-keyed
    PinnedStore (DESIGN.md §10) — its residency is the mesh sharding
    itself, and the PlanCache's mesh fingerprint invalidates plans that
    embed it when the mesh changes.
    """
    from repro.kernels.octent import ops as oct_ops
    mesh, axes = _resolve_mesh(mesh, axes)
    s = math.prod(int(mesh.shape[a]) for a in axes)
    qt = oct_ops.build_query_table(coords, batch, valid,
                                   max_blocks=max_blocks,
                                   grid_bits=grid_bits,
                                   batch_bits=batch_bits,
                                   binning_mode=binning_mode)
    sentinel = max_blocks * morton.TABLE_SIZE
    mb = -(-max_blocks // s) * s
    n_pad = -(-qt.tkey.shape[0] // (s * LANE)) * (s * LANE)
    ublocks = _pad_sorted(qt.ublocks, mb, mapsearch.INVALID)
    tkey = _pad_sorted(qt.tkey, n_pad, sentinel)
    tval = _pad_sorted(qt.tval, n_pad, -1)
    bounds = jnp.concatenate(
        [ublocks[:: mb // s], jnp.full((1,), mapsearch.INVALID, jnp.int32)])
    tbounds = jnp.concatenate(
        [tkey[:: n_pad // s], jnp.full((1,), sentinel, jnp.int32)])
    spec = P(axes if len(axes) > 1 else axes[0])
    return ShardedQueryTable(
        ublocks=_pin(ublocks, mesh, spec), n_blocks=qt.n_blocks,
        tkey=_pin(tkey, mesh, spec), tval=_pin(tval, mesh, spec),
        bounds=bounds, tbounds=tbounds, n_shards=s, axes=axes)


def owner_shard(bounds: jnp.ndarray, bkey: jnp.ndarray) -> jnp.ndarray:
    """Which key range owns each block key — one lower-bound against the
    shard boundaries (the Query Transmitter's routing function)."""
    return jnp.searchsorted(bounds[1:], bkey, side="right").astype(jnp.int32)


def _partial_query(ub_loc, rank_base, tkey_loc, tval_loc,
                   coords, batch, valid, offsets, *, grid_bits,
                   axes, return_partials):
    """shard_map body: answer every query from this shard's key range.

    Mirrors ref.octent_query_ref stage for stage (the query math *is*
    ref.encode_queries), except both lower-bound searches walk the
    *local* slices and each stage's result is published with a pmax
    merge (misses are -1, at most one shard hits).
    """
    inb, bkey, bank, row = encode_queries(coords, batch, valid, offsets,
                                          grid_bits=grid_bits)

    # stage 1: local directory slice -> owner publishes the global rank.
    # An exact match against a live slice entry is the ownership test
    # (bounds[s] <= bkey < bounds[s+1] iff the key sorts into slice s).
    b = ub_loc.shape[0]
    r = jnp.searchsorted(ub_loc, bkey).astype(jnp.int32)
    rc = jnp.minimum(r, b - 1)
    hit_dir = (r < b) & (ub_loc[rc] == bkey)
    rank = jax.lax.pmax(jnp.where(hit_dir, rank_base[0] + rc, -1), axes)
    hit_b = rank >= 0

    # stage 2: local table slice. tkey entries are global flat addresses,
    # so slicing changes nothing about the match test.
    key2 = jnp.where(hit_b,
                     rank * morton.TABLE_SIZE + bank * morton.BANK_ROWS + row,
                     -1)
    n_t = tkey_loc.shape[0]
    pos = jnp.minimum(jnp.searchsorted(tkey_loc, key2).astype(jnp.int32),
                      n_t - 1)
    hit = hit_b & inb & (tkey_loc[pos] == key2)
    partial = jnp.where(hit, tval_loc[pos], -1)
    kmap = jax.lax.pmax(partial, axes)
    if return_partials:
        return kmap, jnp.where(hit_dir, rank_base[0] + rc, -1), partial
    return kmap


@functools.partial(jax.jit, static_argnames=("mesh", "axes", "grid_bits",
                                             "return_partials"))
def _query_on_mesh(ub, rank_base, tkey, tval, coords, batch, valid, offsets,
                   *, mesh, axes, grid_bits, return_partials):
    """The shard_map'd query, jitted so an eager call compiles once per
    (mesh, shapes) and not once per call."""
    ax = axes if len(axes) > 1 else axes[0]
    fn = shard_map(
        functools.partial(_partial_query, grid_bits=grid_bits, axes=axes,
                          return_partials=return_partials),
        mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(ax), P(), P(), P(), P()),
        out_specs=(P(), P(ax), P(ax)) if return_partials else P(),
        check_vma=False)
    return fn(ub, rank_base, tkey, tval, coords, batch, valid, offsets)


def octent_query_sharded(coords: jnp.ndarray, batch: jnp.ndarray,
                         valid: jnp.ndarray, offsets: jnp.ndarray,
                         sqt: ShardedQueryTable, *, grid_bits: int = 7,
                         batch_bits: int = 4, mesh=None,
                         return_partials: bool = False):
    """Resolve all K offset queries per voxel over the mesh.

    Returns (kmap (N, K) int32, n_blocks ()). ``n_blocks`` comes from
    the replicated stage-1 build, so it is identical on every shard
    already — the overflow signal needs no reduce. ``return_partials``
    additionally returns the (S, N, K) pre-merge per-shard answers of
    both stages (directory ranks, table lookups) for routing tests:
    stage 1 must be answered by the ``bounds`` owner, stage 2 by the
    ``tbounds`` owner.
    """
    mesh, axes = _resolve_mesh(mesh, sqt.axes)
    s = sqt.n_shards
    rank_base = jnp.arange(s, dtype=jnp.int32) * (sqt.ublocks.shape[0] // s)
    out = _query_on_mesh(sqt.ublocks, rank_base, sqt.tkey, sqt.tval, coords,
                         batch.astype(jnp.int32), valid,
                         offsets.astype(jnp.int32), mesh=mesh, axes=axes,
                         grid_bits=grid_bits,
                         return_partials=return_partials)
    nb = jnp.asarray(sqt.n_blocks, jnp.int32)
    if return_partials:
        kmap, pranks, partials = out
        n, k = coords.shape[0], offsets.shape[0]
        return kmap, nb, pranks.reshape(s, n, k), partials.reshape(s, n, k)
    return out, nb


def require_blockkey_mesh(mesh=None, axes: tuple | None = None):
    """Validate that a usable mesh exists, raising the configuration
    ValueError otherwise. Called *eagerly* by ops.build_kmap before the
    guarded dispatch (DESIGN.md §11): a missing/axis-less mesh is a
    configuration error, not an execution failure — it must surface to
    the caller instead of being silently served by the fallback chain."""
    return _resolve_mesh(mesh, axes)


def build_kmap_sharded(coords: jnp.ndarray, batch: jnp.ndarray,
                       valid: jnp.ndarray, *, max_blocks: int,
                       grid_bits: int = 7, batch_bits: int = 4,
                       offsets: jnp.ndarray | None = None,
                       binning_mode: str = "counting", mesh=None,
                       axes: tuple | None = None
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Submanifold OCTENT map search over the active mesh.

    Same contract as ops.build_kmap (and bit-identical output): returns
    (kmap (N, K) int32 with -1 misses, n_blocks) — n_blocks from the
    replicated stage-1 build (shard-uniform) for the caller's overflow
    check.
    """
    mesh, axes = _resolve_mesh(mesh, axes)
    if offsets is None:
        offsets = jnp.asarray(morton.subm3_offsets())
    sqt = build_query_table_sharded(coords, batch, valid,
                                    max_blocks=max_blocks,
                                    grid_bits=grid_bits,
                                    batch_bits=batch_bits,
                                    binning_mode=binning_mode,
                                    mesh=mesh, axes=axes)
    return octent_query_sharded(coords, batch, valid, offsets, sqt,
                                grid_bits=grid_bits, batch_bits=batch_bits,
                                mesh=mesh)
