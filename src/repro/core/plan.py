"""Execution plans: memoized map search + tiling for rulebook execution.

The paper reuses the Map Table across layers that share a coordinate set
(§IV-D2: Tconv2 reloads the exported Gconv2 maps instead of re-searching).
This module generalizes that to *every* coordinate-preserving layer: a
:class:`ConvPlan` bundles everything about a convolution that depends only
on geometry — the kernel map plus the tap-sorted tile streams — and a
:class:`PlanCache` memoizes plans per coordinate set, so a stage of B
stacked Subm3 blocks pays for OCTENT once instead of B times, and a
MinkUNet decoder stage at resolution r reuses the encoder-stage plan for
the same r (coordinates recovered exactly by Tconv2).

What is cacheable and what is not (DESIGN.md §4, §10):

  * kmap / tiles / tap schedule   — geometry-only, cached.
  * SPAC liveness (tile_nz)       — depends on the post-ReLU zero pattern of
    the *current* features, refreshed per layer by ops.refresh_liveness
    (a no-op check when every row a valid map reads is live).

Cache keys come in two forms (DESIGN.md §10):

  * **identity keys** (the fast path) — object ids of the coordinate
    arrays plus the static search parameters plus the active mesh's
    fingerprint. Exactly right under jit: stacked blocks see the *same*
    tracer objects for coords/batch/valid (feats-only updates go through
    ``SparseTensor._replace``), and tracers admit no content hashing
    anyway.
  * **content keys** — a cheap device-side fingerprint of the key arrays
    (:func:`array_fingerprint`: a jitted position-mixed XOR/sum/weighted-
    sum reduction over the raw int words, plus shape/dtype). Computed
    only for concrete arrays, on an identity miss. This is what makes
    the cache work *across training steps*: a dataloader replaying the
    same cloud, or a donated buffer re-allocated at the same content,
    lands on the same plan even though every array object is new.

The mesh fingerprint makes both keys mesh-aware: a plan built under one
mesh embeds that mesh's sharded search (and its collectives), so the same
coordinate arrays under a different mesh shape rebuild instead of
replaying a stale partitioning. Entries pin their key arrays so ids
cannot be recycled while the entry lives; capacity-bounded FIFO.

Hit/miss behavior is fully observable: ``PlanCache.stats()`` reports
``id_hits`` / ``content_hits`` / ``misses`` / ``collisions``.
Fingerprints are 96 bits per array plus shape/dtype, so accidental
collisions are vanishingly rare; construct the cache with ``verify=True``
to additionally compare the arrays element-wise on every content hit
(collisions are then counted and rebuilt instead of served stale).
``REPRO_PLANCACHE_CONTENT=0`` disables content keys process-wide
(identity-only, the pre-PR-5 behavior) — see runtime/flags.py.

The content tier can additionally be made *durable* (DESIGN.md §13):
construct with ``persist=SnapshotStore(...)`` and content-keyed builds
write through to disk atomically while content misses read through —
a restarted process replays previously-seen geometries with zero map
searches. ``save()``/``load()`` bulk-flush and rehydrate.

The PlanCache cooperates with the **pinned tier** of the non-uniform
caching policy (runtime/feature_cache.py): on a plan build, the small
OCTENT search structure (directory + compacted table) is pinned in a
byte-bounded :class:`~repro.runtime.feature_cache.PinnedStore` keyed by
the same content fingerprint, so even after the plan itself is evicted, a
rebuild of the same geometry skips the stage-1 table build and only
re-runs the query. Features and weights are stream-tier and never cached.

``MAPSEARCH_CALLS`` counts actual map-search invocations (trace-time), so
tests can assert a 4-block stage searches once and a two-step training
loop over a re-allocated identical cloud searches zero extra times.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import mapsearch, morton, rulebook, sparsity, validate
from repro.core.mapsearch import StridedMaps
from repro.kernels.spconv_gemm import ops as sg_ops
from repro.runtime import fault, feature_cache, guard, sharding


def _octent_ops():
    # deferred: kernels/octent itself imports repro.core (morton/binning),
    # so a module-level import here would cycle when the octent package is
    # the first thing a process imports
    from repro.kernels.octent import ops as oct_ops
    return oct_ops

MAPSEARCH_CALLS = [0]

#: subm3 plans assembled from a streaming delta patch instead of a full
#: map search (DESIGN.md §15) — the warm-start sibling of
#: MAPSEARCH_CALLS, so streaming tests can assert a small-delta frame
#: patched rather than searched.
DELTA_PATCHES = [0]


def mapsearch_call_count() -> int:
    """Map-search invocations since the last reset (trace-time count)."""
    return MAPSEARCH_CALLS[0]


def reset_mapsearch_counter() -> None:
    MAPSEARCH_CALLS[0] = 0


def delta_patch_count() -> int:
    """Warm-started (delta-patched) subm3 builds since the last reset."""
    return DELTA_PATCHES[0]


def reset_delta_patch_counter() -> None:
    DELTA_PATCHES[0] = 0


# ---------------------------------------------------------------------------
# Content fingerprinting (DESIGN.md §10)
# ---------------------------------------------------------------------------

def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """lowbias32 finalizer: diffuse every input bit over all 32 output
    bits, so a single-voxel perturbation flips ~half the fingerprint."""
    x = x.astype(jnp.uint32)
    x ^= x >> 16
    x *= jnp.uint32(0x7FEB352D)
    x ^= x >> 15
    x *= jnp.uint32(0x846CA68B)
    x ^= x >> 16
    return x


@jax.jit
@jax.named_scope("plan.fingerprint")
def _fp_words(flat: jnp.ndarray) -> jnp.ndarray:
    """(3,) uint32 fingerprint words of a flat int32 array.

    Position-mixed so the reduction is order-*sensitive* (a permuted
    voxel list is a different rulebook): each word is hashed together
    with its index before the XOR / sum / odd-weighted-sum reductions.
    Runs entirely on device under jit; only the 3 words travel to host.
    """
    idx = jnp.arange(flat.shape[0], dtype=jnp.uint32)
    h = _mix32(flat.astype(jnp.uint32) ^ _mix32(idx))
    xor = jax.lax.reduce(h, jnp.uint32(0), jax.lax.bitwise_xor, (0,))
    tot = jnp.sum(h, dtype=jnp.uint32)
    wtot = jnp.sum(h * (2 * idx + 1), dtype=jnp.uint32)
    return jnp.stack([xor, tot, wtot])


def array_fingerprint(a) -> tuple | None:
    """Content fingerprint of one key array, or None if unhashable.

    Returns ``(shape, dtype_str, w0, w1, w2)`` for concrete integer/bool
    arrays — 96 mixed bits plus the exact structure, cheap enough to run
    per lookup (one jitted reduction, three scalars to host). Returns
    None for tracers (under jit the identity fast path is both correct
    and the only option) and for float arrays (plan keys are integral by
    construction; refusing keeps the cache conservative rather than
    wrong about NaN/-0.0 equality).
    """
    if isinstance(a, jax.core.Tracer):
        return None
    if not hasattr(a, "dtype"):
        a = jnp.asarray(a)
    if not (jnp.issubdtype(a.dtype, jnp.integer)
            or jnp.issubdtype(a.dtype, jnp.bool_)):
        return None
    # an enclosing jit must not capture the reduction: plans for concrete
    # (closed-over) coordinate arrays are still content-addressable at
    # trace time, so force compile-time evaluation
    with jax.ensure_compile_time_eval():
        if a.dtype.itemsize > 4:
            # int64 under x64: hash every 32-bit word, never truncate —
            # values equal mod 2^32 must not collide systematically
            flat = jnp.ravel(jax.lax.bitcast_convert_type(a, jnp.int32))
        else:
            flat = jnp.ravel(a).astype(jnp.int32)
        words = np.asarray(_fp_words(flat))
        guard.health().note("plan.host_sync")
    # chaos hook: the 'fingerprint' fault site corrupts the words to
    # model a content-key collision (runtime/fault.py); a verifying
    # cache detects the mismatch and rebuilds instead of serving stale
    words = fault.mangle("fingerprint", words)
    return (tuple(a.shape), str(a.dtype),
            int(words[0]), int(words[1]), int(words[2]))


def content_fingerprint(arrays) -> tuple | None:
    """Fingerprint a tuple of key arrays; None if any is unhashable."""
    words = []
    for a in arrays:
        w = array_fingerprint(a)
        if w is None:
            return None
        words.append(w)
    return tuple(words)


def _content_enabled() -> bool:
    # re-read per cache construction, not frozen at import (flags.py)
    return os.environ.get("REPRO_PLANCACHE_CONTENT", "1") != "0"


class ConvPlan(NamedTuple):
    """Geometry-only execution plan for one SpConv layer.

    ``kmap`` is the gather-form rulebook; ``tiles`` its tap-scheduled,
    bm-padded tile streams (no row elision folded in — see module doc).
    ``out_*`` are None for coordinate-preserving layers (outputs == inputs);
    ``maps`` carries the scatter-form triples for strided layers so Tconv2
    and the input-stationary dataflow can reuse them.
    """

    kind: str                      # subm3 | gconv2 | gconv3 | tconv2
    kmap: jnp.ndarray              # (N_out, K)
    tiles: sg_ops.TapTiles | None  # None when built for a dataflow that
                                   # never tiles (input-stationary gconv3)
    n_out: int                     # static output row budget
    n_taps: int
    out_coords: jnp.ndarray | None
    out_batch: jnp.ndarray | None
    out_valid: jnp.ndarray | None
    maps: StridedMaps | None
    overflow: jnp.ndarray | None = None  # () bool: capacity overflowed —
                                         # subm3 block table or gconv3
                                         # candidate budget (set under jit;
                                         # eager builds raise
                                         # validate.CapacityOverflow)
    sites: int | None = None  # gconv3: the true output-site count, read
                              # by the eager capacity check (None under
                              # jit); a Python int, so strip it before
                              # passing the plan through jit as arguments

    @property
    def residency(self) -> dict:
        """Bytes per caching tier of this plan (DESIGN.md §10): the
        pinned per-tile metadata vs the cached kmap/slot streams. The
        search table is accounted separately (it lives in the
        PinnedStore, not on the plan)."""
        return feature_cache.plan_tier_bytes(self)


class _Entry(NamedTuple):
    """One canonical cache entry: the plan plus the anchored key arrays
    of every identity alias pointing at it (anchoring keeps the ids from
    being recycled while the alias is live)."""

    plan: ConvPlan
    aliases: OrderedDict        # idkey -> anchored array tuple
    fingerprint: tuple | None   # content words (no statics), for verify


#: identity aliases kept per canonical entry before the oldest is dropped
#: (a long-running loop over re-allocated clouds would otherwise anchor
#: every step's arrays forever)
ALIAS_CAP = 8


class PlanCache:
    """Content-addressed memo of ConvPlans with an identity fast path.

    One instance per forward pass (models create their own), or
    longer-lived for eager/incremental pipelines and training loops —
    cross-step reuse is exactly what the content keys are for (module
    doc). Entries hold strong references to their key arrays, so an id
    is never reused while its alias is alive.

    Args:
      capacity: canonical entries kept (FIFO eviction).
      content: enable content-addressed keys for concrete arrays
        (default: on, unless ``REPRO_PLANCACHE_CONTENT=0``).
      verify: on every content hit, compare the key arrays element-wise
        against the entry's anchored arrays; a mismatch counts as a
        ``collision`` and rebuilds (replacing the entry) instead of
        serving a stale plan.
      pinned: the :class:`~repro.runtime.feature_cache.PinnedStore` for
        the pinned tier (None: the process-wide default store).
      persist: a :class:`~repro.runtime.persist.SnapshotStore` making the
        content tier durable (DESIGN.md §13): a content-key miss reads
        through to disk before building (a verified on-disk plan costs
        zero map searches), and every content-keyed build writes through
        atomically. Identity-only entries (tracer keys) are never
        persisted — object ids mean nothing across processes.

    Counters: ``hits`` (total), ``id_hits``, ``content_hits``,
    ``persist_hits``, ``misses``, ``collisions`` — see :meth:`stats`.
    """

    def __init__(self, capacity: int = 64, *, content: bool | None = None,
                 verify: bool = False,
                 pinned: feature_cache.PinnedStore | None = None,
                 persist=None):
        self.capacity = capacity
        self.content = _content_enabled() if content is None else content
        self.verify = verify
        self.pinned = pinned if pinned is not None \
            else feature_cache.default_store()
        self.persist = persist
        self._entries: OrderedDict = OrderedDict()  # canonical key -> _Entry
        self._by_id: dict = {}                      # identity key -> canonical
        self.hits = 0
        self.misses = 0
        self.id_hits = 0
        self.content_hits = 0
        self.persist_hits = 0
        self.collisions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> dict:
        """Counter snapshot (plus the pinned store's, for one-stop
        observability of the whole §10 policy)."""
        return {"entries": len(self), "hits": self.hits,
                "id_hits": self.id_hits, "content_hits": self.content_hits,
                "persist_hits": self.persist_hits,
                "misses": self.misses, "collisions": self.collisions,
                "pinned": self.pinned.stats()}

    # -- durability (DESIGN.md §13) -----------------------------------------

    def save(self, persist=None) -> int:
        """Flush every content-keyed entry to the snapshot store; returns
        the number committed. With write-through active this is a no-op
        flush for entries built before ``persist`` was attached (e.g. a
        cache handed to :meth:`save` at shutdown)."""
        store = persist if persist is not None else self.persist
        if store is None:
            return 0
        n = 0
        for ckey, entry in self._entries.items():
            if entry.fingerprint is None:
                continue
            fp, statics = ckey
            if store.put(("plan", fp, statics), entry.plan):
                n += 1
        return n

    def load(self, persist=None) -> int:
        """Rehydrate every verified on-disk plan into the content tier;
        returns the number loaded. Corrupt/stale entries are dropped by
        the store (``persist.dropped``), never raised. Loaded plans have
        no identity aliases yet — the first lookup content-hits and
        aliases as usual, with **zero** map searches."""
        store = persist if persist is not None else self.persist
        if store is None:
            return 0
        n = 0
        for key, value in store.items():
            if not (isinstance(key, tuple) and len(key) == 3
                    and key[0] == "plan"):
                continue
            ckey = (key[1], key[2])
            if ckey in self._entries:
                continue
            self._evict_to_capacity()
            self._entries[ckey] = _Entry(value, OrderedDict(), key[1])
            n += 1
        return n

    # -- internals ----------------------------------------------------------

    def _evict_to_capacity(self) -> None:
        while len(self._entries) >= self.capacity:
            _, entry = self._entries.popitem(last=False)
            for idkey in entry.aliases:
                self._by_id.pop(idkey, None)

    def _alias(self, canonical, idkey, arrays) -> None:
        entry = self._entries[canonical]
        if idkey in entry.aliases:
            return
        entry.aliases[idkey] = tuple(arrays)
        self._by_id[idkey] = canonical
        while len(entry.aliases) > ALIAS_CAP:
            old, _ = entry.aliases.popitem(last=False)
            self._by_id.pop(old, None)

    def _verify_hit(self, entry: _Entry, arrays) -> bool | None:
        """Element-wise compare against an anchored alias's arrays.

        Returns True/False on a live comparison, or None when every
        anchored alias has been donated/deleted (the donated-buffer
        training pattern invalidates buffers the entry still references)
        — the caller then rebuilds rather than crashing or serving an
        unverifiable plan.
        """
        for anchored in reversed(entry.aliases.values()):   # newest first
            ok = feature_cache.anchors_match(anchored, arrays)
            if ok is not None:
                return ok
        return None

    # -- lookup -------------------------------------------------------------

    def lookup(self, arrays, statics, build):
        """Memoized plan for ``(arrays, statics)`` under the active mesh.

        ``build(fingerprint)`` is called on a miss; ``fingerprint`` is
        the content words of ``arrays`` (or None under trace / with
        content keys disabled) so the builder can key its pinned-tier
        structures off the same identity (subm3_plan does).
        """
        statics = tuple(statics) + sharding.mesh_fingerprint()
        idkey = (tuple(id(a) for a in arrays), statics)
        canonical = self._by_id.get(idkey)
        if canonical is not None and canonical in self._entries:
            self.hits += 1
            self.id_hits += 1
            return self._entries[canonical].plan

        fp = None
        if self.content:
            with TraceAnnotation("plan.fingerprint"):
                fp = content_fingerprint(arrays)
        if fp is not None:
            ckey = (fp, statics)
            entry = self._entries.get(ckey)
            if entry is not None:
                ok = self._verify_hit(entry, arrays) if self.verify else True
                if ok:
                    self.hits += 1
                    self.content_hits += 1
                    self._alias(ckey, idkey, arrays)
                    return entry.plan
                if ok is False:
                    self.collisions += 1
                # ok False: collision; ok None: anchors all donated —
                # either way rebuild instead of serving unverified
                self._entries.pop(ckey)            # latest wins
                for ik in entry.aliases:
                    self._by_id.pop(ik, None)
        else:
            ckey = idkey                           # identity-only entry

        plan = None
        if fp is not None and self.persist is not None:
            # durable read-through: a verified on-disk plan for this
            # content key replays with zero map searches (DESIGN.md §13)
            plan = self.persist.get(("plan", fp, statics))
        if plan is not None:
            self.hits += 1
            self.persist_hits += 1
        else:
            self.misses += 1
            plan = build(fp)
            if fp is not None and self.persist is not None:
                self.persist.put(("plan", fp, statics), plan)
        self._evict_to_capacity()
        self._entries[ckey] = _Entry(plan, OrderedDict(), fp)
        self._alias(ckey, idkey, arrays)
        return plan


def _maybe_cached(cache: PlanCache | None, arrays, statics, build):
    if cache is None:
        return build(None)
    return cache.lookup(arrays, statics, build)


# ---------------------------------------------------------------------------
# Plan builders — one per layer type
# ---------------------------------------------------------------------------

def _require_block_capacity(n_blocks, max_blocks: int):
    """Surface octree-table overflow instead of silently dropping voxels.

    The table build scatters with mode='drop': a scene with more occupied
    16^3 blocks than ``max_blocks`` would quietly lose every map touching
    the dropped blocks (the sibling of the grid_bits clamp PR 1 outlawed
    for the sorted variant). Eagerly this raises; under jit the comparison
    is a tracer, so the flag is returned and carried on the plan
    (``ConvPlan.overflow``) for the caller to assert on.
    """
    overflow = jnp.asarray(n_blocks, jnp.int32) > max_blocks
    try:
        with TraceAnnotation("plan.check"):
            concrete = bool(overflow)
    except jax.errors.ConcretizationTypeError:
        return overflow
    guard.health().note("plan.host_sync")
    if concrete:
        raise validate.CapacityOverflow(
            "block_table",
            f"octree block table overflow: the scene occupies "
            f"{int(n_blocks)} 16^3 blocks but max_blocks={max_blocks}; "
            f"voxels in the dropped blocks would silently lose their maps "
            f"— raise max_blocks (or coarsen the scene, or wrap the build "
            f"in runtime/guard.with_replan)",
            needed=int(n_blocks), capacity=max_blocks)
    return overflow


def _require_out_capacity(overflow_flag, n_true, budget: int):
    """Surface Gconv3 candidate-space overflow (the mapsearch.py
    truncation sibling of :func:`_require_block_capacity`). Returns
    ``(overflow, sites)``: eagerly ``sites`` is the true output-site
    count, read in the one host read of the check, and an overflow raises
    :class:`~repro.core.validate.CapacityOverflow`; under jit ``sites``
    is None and the () bool flag is carried on ``ConvPlan.overflow``."""
    overflow = jnp.asarray(overflow_flag, bool)
    try:
        with TraceAnnotation("plan.check"):
            sites = int(n_true)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerIntegerConversionError):
        return overflow, None
    guard.health().note("plan.host_sync")
    if sites > budget:
        raise validate.CapacityOverflow(
            "candidates",
            f"gconv3 candidate budget overflow: the cloud produces "
            f"{sites} downsampled output sites but out_budget={budget}; "
            f"the overflowing sites would silently lose their maps — raise "
            f"out_budget (or wrap the build in runtime/guard.with_replan)",
            needed=sites, capacity=budget)
    return overflow, sites


class SubmWarmStart(NamedTuple):
    """Delta warm-start for :func:`subm3_plan` (DESIGN.md §15).

    ``patch()`` produces ``(kmap, table)`` for the *new* frame's
    coordinate arrays by incrementally updating the previous frame's
    structures (core/stream.py: directory/table splice + dirty-row
    re-query) — bit-identical to a from-scratch build over the same
    arrays, but paying only for the changed neighborhoods. It is only
    invoked on a cache miss: the statics are unchanged from the scratch
    build, so the content key of the new arrays is what distinguishes
    "same geometry" (content hit — neither searched nor patched) from
    "small delta" (miss — patched in place of a full search).
    """

    patch: object   # () -> (kmap (N, 27) int32, octent ops.QueryTable)


def subm3_plan(coords, batch, valid, *, max_blocks: int,
               method: str = "octree", grid_bits: int = 7,
               batch_bits: int = 4, bm: int = 128, bo: int | None = None,
               search_impl: str | None = None,
               cache: PlanCache | None = None,
               warm: SubmWarmStart | None = None) -> ConvPlan:
    """Submanifold 3x3x3 plan: outputs == inputs, 27 taps.

    Args:
      coords, batch, valid: the padded coordinate stream (N, 3)/(N,)/(N,).
      max_blocks: octree directory capacity; the builder raises (eager)
        or sets ``ConvPlan.overflow`` (jit) when the scene occupies more
        16^3 blocks — never a silent voxel drop.
      method: 'octree' (the paper engine) | 'sorted' (beyond-paper
        composite-key variant, small grids only).
      grid_bits, batch_bits: block-key bit budget (core/morton.py).
      bm: kernel m-tile rows; ``bo``: output-block height of the
        output-stationary tile layout (DESIGN.md §5/§6; None = build
        default).
      search_impl: OCTENT backend — pallas | interpret | ref | xla |
        sharded; None resolves via ``octent.ops.search_impl()`` (the
        mesh-partitioned engine when the active mesh shards the
        block-key axes, else the Pallas kernel on TPU / its XLA
        bit-oracle elsewhere). 'xla' is the retained dense-table builder.
      cache: memoize per coordinate set (identity + content keys).
      warm: a :class:`SubmWarmStart` whose ``patch()`` supplies
        ``(kmap, table)`` incrementally from the previous frame
        (DESIGN.md §15). Consulted only on a cache miss, and only for
        the table-backed octree impls — other impls ignore it and build
        from scratch. ``warm`` is deliberately *not* part of the cache
        key: a patched plan is bit-identical to the scratch plan for the
        same arrays, so both may serve the same key.

    Returns:
      A :class:`ConvPlan` with kind='subm3', 27 taps, out_* = None.

    The resolved impl is part of the cache key, alongside the mesh
    fingerprint; on the sharded path ``n_blocks`` — and therefore
    ``ConvPlan.overflow`` — comes from the replicated stage-1 build, so
    every shard sees the same flag. On the table-backed impls
    (pallas/interpret/ref) the stage-1 QueryTable is pinned in the
    cache's :class:`~repro.runtime.feature_cache.PinnedStore` keyed by
    the content fingerprint, so a rebuild after plan eviction skips
    straight to the query (DESIGN.md §10).
    """
    simpl = (search_impl or _octent_ops().search_impl()) \
        if method == "octree" else None
    statics = ("subm3", max_blocks, method, simpl, grid_bits, batch_bits,
               bm, bo)
    store = cache.pinned if cache is not None else None

    def build(fp):
        fault.check("plan")
        oct_ops = _octent_ops()
        offs = jnp.asarray(morton.subm3_offsets())
        overflow = None
        if method == "octree":
            table = None
            pin_key = None
            # anchoring the key arrays costs device memory against the
            # store budget, so only verifying caches pay for it
            verify = cache is not None and cache.verify
            anchor = (coords, batch, valid) if verify else None
            if simpl in ("pallas", "interpret", "ref") and fp is not None \
                    and store is not None:
                pin_key = ("qtable", fp, max_blocks, grid_bits, batch_bits,
                           sharding.mesh_fingerprint())
                table = store.get(pin_key, anchor=anchor, verify=verify)
            if warm is not None and simpl in ("pallas", "interpret", "ref"):
                # streaming warm start (DESIGN.md §15): the patch derives
                # the new frame's structures from the previous frame's —
                # any dirty-row queries it runs are counted by
                # octent.ops.QUERY_ROWS, not as a full map search
                DELTA_PATCHES[0] += 1
                with TraceAnnotation("plan.search"):
                    kmap, table = warm.patch()
                overflow = _require_block_capacity(table.n_blocks,
                                                   max_blocks)
                if pin_key is not None:
                    store.put(pin_key, table, anchor=anchor)
            else:
                MAPSEARCH_CALLS[0] += 1
                with TraceAnnotation("plan.search"):
                    if simpl in ("pallas", "interpret", "ref") \
                            and table is None:
                        table = oct_ops.build_query_table(
                            coords, batch, valid, max_blocks=max_blocks,
                            grid_bits=grid_bits, batch_bits=batch_bits)
                        if pin_key is not None:
                            store.put(pin_key, table, anchor=anchor)
                    kmap, n_blocks = oct_ops.build_kmap(
                        coords, batch, valid, max_blocks=max_blocks,
                        grid_bits=grid_bits, batch_bits=batch_bits,
                        impl=simpl, offsets=offs, table=table)
                overflow = _require_block_capacity(n_blocks, max_blocks)
        elif method == "sorted":
            MAPSEARCH_CALLS[0] += 1
            if not mapsearch.sorted_key_fits(grid_bits, batch_bits):
                raise ValueError(
                    f"map search method 'sorted' needs the composite key "
                    f"(3*grid_bits + batch_bits + {morton.LOCAL_CODE_BITS}) "
                    f"to fit int32, got grid_bits={grid_bits}, "
                    f"batch_bits={batch_bits} -> "
                    f"{3 * grid_bits + batch_bits + morton.LOCAL_CODE_BITS} "
                    f"bits. Pass grid_bits <= "
                    f"{(31 - batch_bits - morton.LOCAL_CODE_BITS) // 3} or "
                    f"use method='octree' for large grids.")
            with TraceAnnotation("plan.search"):
                kmap = mapsearch.build_kmap_sorted(
                    coords, batch, valid, offs,
                    grid_bits=grid_bits, batch_bits=batch_bits)
        else:
            raise ValueError(f"unknown map search method {method!r}")
        with TraceAnnotation("plan.tiles"):
            tiles = sg_ops.build_tap_tiles(kmap, None, bm=bm, bo=bo)
        return ConvPlan("subm3", kmap, tiles, coords.shape[0], 27,
                        None, None, None, None, overflow)

    return _maybe_cached(cache, (coords, batch, valid), statics, build)


def gconv2_plan(coords, batch, valid, *, grid_bits: int = 7,
                batch_bits: int = 4, bm: int = 128, bo: int | None = None,
                cache: PlanCache | None = None) -> ConvPlan:
    """Gconv2 (k=2, s=2) plan: octant taps to octree parents (§IV-D1).

    Returns a ConvPlan carrying the downsampled ``out_*`` coordinate set
    and the scatter-form ``maps`` the paired Tconv2 reuses (§IV-D2).
    """
    statics = ("gconv2", grid_bits, batch_bits, bm, bo)

    def build(fp):
        fault.check("plan")
        MAPSEARCH_CALLS[0] += 1
        n = coords.shape[0]
        with TraceAnnotation("plan.search"):
            maps = mapsearch.build_maps_gconv2(coords, batch, valid,
                                               grid_bits=grid_bits,
                                               batch_bits=batch_bits)
            kmap = mapsearch.strided_to_kmap(maps, n_out=n, n_taps=8)
        with TraceAnnotation("plan.tiles"):
            tiles = sg_ops.build_tap_tiles(kmap, None, bm=bm, bo=bo)
        return ConvPlan("gconv2", kmap, tiles, n, 8,
                        maps.out_coords, maps.out_batch, maps.out_valid, maps)

    return _maybe_cached(cache, (coords, batch, valid), statics, build)


def gconv3_plan(coords, batch, valid, *, grid_bits: int = 7,
                batch_bits: int = 4, out_budget: int | None = None,
                bm: int = 128, bo: int | None = None,
                with_tiles: bool = True,
                kernel: tuple = (3, 3, 3), stride: tuple = (2, 2, 2),
                padding: tuple = (1, 1, 1), extent: tuple | None = None,
                cache: PlanCache | None = None) -> ConvPlan:
    """Strided conv plan (§IV-D3): by default Gconv3 (k=3, s=2, p=1);
    ``kernel``, ``stride``, ``padding`` (per axis, x y z) and the input
    ``extent`` give any strided conv spconv's maps and output-extent crop
    (``mapsearch.build_maps_gconv3``). Carries the scatter maps so the
    input-stationary dataflow can execute from the same plan;
    ``with_tiles=False`` skips the tile build for that dataflow (the tiles
    would be dead weight — it consumes only ``plan.maps``). ``with_tiles``
    is part of the cache key, so a rare mixed-dataflow reuse of one
    coordinate set costs a second search rather than returning a plan
    without the tiles the output-stationary path needs. An eager build
    carries its output-site count on ``ConvPlan.sites``, from the capacity
    check's own host read."""
    budget = out_budget if out_budget is not None else coords.shape[0]
    kernel, stride, padding = tuple(kernel), tuple(stride), tuple(padding)
    extent = None if extent is None else tuple(int(d) for d in extent)
    n_taps = int(np.prod(kernel))
    statics = ("gconv3", grid_bits, batch_bits, budget, bm, bo, with_tiles,
               kernel, stride, padding, extent)

    def build(fp):
        fault.check("plan")
        MAPSEARCH_CALLS[0] += 1
        with TraceAnnotation("plan.search"):
            maps = mapsearch.build_maps_gconv3(
                coords, batch, valid, grid_bits=grid_bits,
                batch_bits=batch_bits, out_budget=budget, kernel=kernel,
                stride=stride, padding=padding, extent=extent)
        overflow, sites = _require_out_capacity(maps.overflow, maps.n_true,
                                                budget)
        with TraceAnnotation("plan.search"):
            kmap = mapsearch.strided_to_kmap(maps, n_out=budget,
                                             n_taps=n_taps)
        tiles = None
        if with_tiles:
            with TraceAnnotation("plan.tiles"):
                tiles = sg_ops.build_tap_tiles(kmap, None, bm=bm, bo=bo)
        return ConvPlan("gconv3", kmap, tiles, budget, n_taps,
                        maps.out_coords, maps.out_batch, maps.out_valid, maps,
                        overflow, sites)

    return _maybe_cached(cache, (coords, batch, valid), statics, build)


def tconv2_plan(gconv2_maps: StridedMaps, target_coords, target_batch,
                target_valid, *, bm: int = 128, bo: int | None = None,
                cache: PlanCache | None = None) -> ConvPlan:
    """Tconv2 plan: transposes the paired Gconv2 maps (§IV-D2 — map *reuse*,
    so this never counts as a map search)."""
    statics = ("tconv2", bm, bo)

    def build(fp):
        n = target_valid.shape[0]
        with TraceAnnotation("plan.search"):
            maps = mapsearch.transpose_maps(gconv2_maps, target_coords,
                                            target_batch, target_valid)
            kmap = mapsearch.strided_to_kmap(maps, n_out=n, n_taps=8)
        with TraceAnnotation("plan.tiles"):
            tiles = sg_ops.build_tap_tiles(kmap, None, bm=bm, bo=bo)
        return ConvPlan("tconv2", kmap, tiles, n, 8,
                        target_coords, target_batch, target_valid, maps)

    keys = (gconv2_maps.in_idx, gconv2_maps.out_idx, gconv2_maps.tap,
            gconv2_maps.mvalid, target_coords, target_batch, target_valid)
    return _maybe_cached(cache, keys, statics, build)


# ---------------------------------------------------------------------------
# Plan execution
# ---------------------------------------------------------------------------

def execute(plan: ConvPlan, feats: jnp.ndarray, weights: jnp.ndarray,
            bias: jnp.ndarray | None = None, *, spac: bool = True,
            act: "sparsity.ActSparsity | None" = None,
            epilogue: "sg_ops.FusedEpilogue | None" = None,
            impl: str | None = None, bn: int = 128,
            src_valid: jnp.ndarray | None = None):
    """Run rulebook execution for ``plan`` over the current features.

    ``feats`` / ``weights`` / ``bias`` are stream-tier by design
    (DESIGN.md §10): they change every layer and step, are never cached,
    and flow through the fused kernel's double-buffered DMAs; everything
    geometry-determined rides on the (cached/pinned) plan.

    impl: 'pallas' | 'interpret' | 'ref' route through the gather-fused
    tile machinery (kernels/spconv_gemm); 'xla' is the pure-XLA tap-scan
    oracle (rulebook.apply_kmap_gather) kept for parity testing. Default
    resolves via ops.kernel_impl().

    ``act`` threads the previous layer's epilogue-emitted ActSparsity as
    the SPAC liveness source (no HBM re-sweep); ``epilogue`` fuses
    BN-inference + ReLU into the execution and changes the return value to
    ``(out, ActSparsity)`` — inference-only, see sg_ops.FusedEpilogue.
    SPAC elision (any grain) is forward-only lossless: every path here
    differentiates through the un-elided geometry math (DESIGN.md §2).

    ``src_valid`` is the (N,) mask of the rows a valid map may read, for
    a Subm3 plan the ``valid`` it was built from: with SPAC on, the
    liveness refresh then short-circuits when every such row is live
    (sg_ops.refresh_liveness, DESIGN.md §14).
    """
    impl = impl or sg_ops.kernel_impl()
    if impl == "xla":
        if spac:
            row_nz = act.row_nz if act is not None \
                else sparsity.row_nonzero(feats)
            # elision via the custom-VJP wrapper: the backward replays the
            # un-compacted kmap (a plain compact_kmap here silently zeroed
            # dfeats for exactly-zero rows)
            out = rulebook.apply_kmap_gather_spac(feats, weights, plan.kmap,
                                                  row_nz)
        else:
            out = rulebook.apply_kmap_gather(feats, weights, plan.kmap)
        if epilogue is not None:
            if bias is not None:
                raise ValueError(
                    "bias and epilogue together would apply the bias twice:"
                    " fold it into the epilogue shift")
            return sg_ops.apply_epilogue_xla(out, epilogue, bn=bn)
        return out + bias if bias is not None else out
    if plan.tiles is None:
        raise ValueError(
            f"{plan.kind} plan was built with with_tiles=False (input-"
            f"stationary dataflow); rebuild it with tiles to execute the "
            f"fused path, or pass impl='xla'")
    row_nz = None
    if spac and act is None:
        row_nz = sparsity.row_nonzero(feats)
    return sg_ops.apply_tiles(feats, weights, plan.tiles, bias,
                              n_out=plan.n_out, row_nz=row_nz,
                              act=act if spac else None, epilogue=epilogue,
                              bn=bn, impl=impl, src_valid=src_valid)
