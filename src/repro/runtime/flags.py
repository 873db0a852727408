"""Global measurement/runtime flags — the one-stop reference.

Environment flags (each entry states *when* its value is read — the two
impl selectors re-read per call so they are never frozen into a trace;
the others bind at construction or import as noted):

``REPRO_SEARCH_IMPL``
    OCTENT map-search backend — ``auto`` (default) | ``pallas`` |
    ``interpret`` | ``ref`` | ``xla`` | ``sharded``. Resolved by
    :func:`repro.kernels.octent.ops.search_impl`: ``auto`` picks the
    mesh-partitioned engine when the active mesh shards the block-key
    axes, else the compiled Pallas kernel on TPU / its XLA bit-oracle
    ``ref`` on any other backend. ``interpret`` runs the same kernel
    under the Pallas interpreter (CPU tests only; nothing on the serve
    path chooses it); ``xla`` is the retained dense-table builder.

``REPRO_KERNEL_IMPL``
    Rulebook-execution backend — ``auto`` (default) | ``pallas`` |
    ``interpret`` | ``ref``. Resolved by
    :func:`repro.kernels.spconv_gemm.ops.kernel_impl`: ``auto`` is the
    compiled fused kernel on TPU, the pure-jnp tile oracle ``ref``
    elsewhere. It is what every entry point uses by default:
    ``spconv_serve --impl auto``, ``ServeEngine(impl=None)``,
    ``train --impl auto`` and ``run_spconv_demo(impl=None)``. A kernel
    that fails to lower raises; it is never served by ``ref`` in its
    place (runtime/guard.py). (The pure-XLA tap scan is not an env
    choice; request it per call with ``impl='xla'``.)

``REPRO_SPAC_BLOCK``
    Set to ``0`` to disable Cin-block-grain SPAC skipping inside live
    tiles (DESIGN.md §14) — the fused kernel then falls back to
    tile-grain skipping only. Forward output is bit-identical either
    way; only the elided row-DMA/MAC work changes. Re-read per call by
    :func:`repro.kernels.spconv_gemm.ops.spac_block_enabled` (never
    frozen into a trace), consumed by
    :func:`repro.kernels.spconv_gemm.ops.apply_tiles`.

``REPRO_PLANCACHE_CONTENT``
    Set to ``0`` to disable content-addressed PlanCache keys process-wide
    (identity-only, the pre-PR-5 behavior; DESIGN.md §10). Read by
    :class:`repro.core.plan.PlanCache` at construction; per-instance
    override via ``PlanCache(content=...)``. Content-hit verification
    (collision detection) is per-instance only: ``PlanCache(verify=True)``.

``REPRO_GUARD_VALIDATE``
    Ingress cloud-sanitizer policy (DESIGN.md §11) — ``repair``
    (default) | ``strict`` | ``off``. Re-read per call by
    :func:`repro.runtime.guard.validate_policy`: ``repair`` invalidates
    /clips/dedups bad rows in place (shapes never change), ``strict``
    raises :class:`repro.core.validate.CloudValidationError` on the
    first defect, ``off`` skips sanitation entirely. Consumed by
    :func:`repro.core.spconv.make_sparse_tensor` and the train demo's
    ingress path.

``REPRO_GUARD_REPLAN``
    Max overflow-adaptive replan escalations (default ``6``; ``0``
    disables — overflows raise). Re-read per call by
    :func:`repro.runtime.guard.replan_retries`; consumed by
    :func:`repro.runtime.guard.with_replan` and (via its default)
    :func:`repro.models.minkunet.build_plans`.

``REPRO_GUARD_FALLBACK``
    Set to ``0`` to disable the backend fallback chain — kernel/search
    dispatch errors then propagate on first failure instead of
    retry → quarantine → serve-the-``ref``-oracle. Re-read per call by
    :func:`repro.runtime.guard.fallback_enabled`; consumed by
    :func:`repro.runtime.guard.dispatch` (wrapping
    ``octent.ops.build_kmap`` and ``spconv_gemm.ops.apply_tiles``).

``REPRO_GUARD_COOLDOWN``
    Calls a quarantined (site, impl, shape-class) sits out before being
    retried (default ``32``). Re-read per call by
    :func:`repro.runtime.guard.fallback_cooldown`.

``REPRO_SERVE_BUCKETS``
    Padding-bucket classes for the serving admission queue (DESIGN.md
    §12) — comma-separated ascending voxel budgets, default
    ``512,1024,2048,4096,8192,16384``. Every admitted request is
    quantized to the smallest bucket that fits, so the engine holds one
    compiled executable per bucket class instead of one per request
    geometry. Re-read per construction by
    :func:`repro.runtime.admission.bucket_classes`.

``REPRO_SERVE_QUEUE_CAP``
    Bounded admission-queue depth (default ``64``); a submit beyond it
    is shed with typed ``queue_full`` backpressure. Read by
    :func:`repro.runtime.admission.queue_capacity`.

``REPRO_SERVE_DEADLINE_MS``
    Default per-request deadline in milliseconds (default ``60000``)
    when ``submit(deadline_s=None)``. Requests whose remaining budget is
    below the engine's per-bucket service estimate are shed at dequeue
    with reason ``deadline``. Read by
    :func:`repro.runtime.admission.default_deadline_s`.

``REPRO_SERVE_MAX_BATCH``
    Requests the serve engine drains per continuous-batching tick
    (default ``8``); the degradation ladder's level 1 halves it. Read
    at :class:`repro.launch.spconv_serve.ServeEngine` construction.

``REPRO_SERVE_VALIDATE``
    Admission sanitizer policy — ``strict`` (default: any defect,
    including ``oversize`` past the largest bucket, is a typed
    rejection) | ``repair`` (defects repaired in place, oversize
    truncated keep-first) | ``off``. Read by
    :func:`repro.runtime.admission.serve_policy`.

``REPRO_PERSIST_DIR``
    Durability root for warm restarts (DESIGN.md §13). When set (and not
    overridden by ``--persist-dir``), ``launch/train.py`` and
    ``launch/spconv_serve.py`` open a
    :class:`repro.runtime.persist.SnapshotStore` under
    ``<dir>/snap`` (durable PlanCache + PinnedStore entries — restarted
    processes replay seen geometries with zero map searches) and the
    serve engine journals admitted requests under ``<dir>/journal``.
    Unset (the default) disables persistence entirely. Read per launch
    by :func:`repro.runtime.persist.default_dir`.

``REPRO_PERSIST_MAX_BYTES``
    On-disk byte budget per snapshot store (default ``268435456`` =
    256 MiB); oldest entries are evicted to admit new ones, and an
    entry larger than the whole budget is skipped. Re-read per store
    construction by :func:`repro.runtime.persist.default_max_bytes`.

``REPRO_PERSIST_VERIFY``
    Set to ``0`` to skip sha256 verification when loading snapshot
    entries (version/salt/key checks always run). Default on — a
    bit-flipped entry is then dropped and counted ``persist.dropped``
    instead of decoded. Re-read per store construction by
    :func:`repro.runtime.persist._verify_enabled`.

``REPRO_PERSIST_SALT``
    Override the snapshot invalidation salt (default: format version +
    codec revision + jax version, :func:`repro.runtime.persist.default_salt`).
    Entries written under a different salt read as stale and cold-start;
    tests use this to model a code-version bump.

``REPRO_STREAM``
    Set to ``0`` to disable the streaming delta path (DESIGN.md §15) —
    every frame of a :class:`repro.core.stream.StreamSession` is then
    rebuilt from scratch (the parity baseline the delta path is gated
    against). Re-read per session construction by
    :func:`repro.core.stream.stream_enabled`; per-instance override via
    ``StreamSession(enabled=...)``. Output is bit-identical either way;
    only the searched-row count changes.

``REPRO_STREAM_MAX_DIRTY``
    Dirty-row fraction above which a streamed frame falls back to a
    full from-scratch rebuild instead of a delta patch (default
    ``0.5`` — at high turnover the table splice plus partial re-query
    costs more than it saves). Re-read per session construction by
    :func:`repro.core.stream.max_dirty_frac`; per-instance override via
    ``StreamSession(dirty_frac=...)``.

``REPRO_BENCH_FAST``
    Set to ``1`` for the reduced benchmark sweep (CI); read by
    ``benchmarks/run.py``.

``REPRO_PROPTEST_CASES``
    Property-test cases per ``@forall`` test (default 25); read **once at
    import** of ``tests/proptest.py`` — set it before pytest starts.

In-process flags:

``UNROLL_FOR_COST``
    XLA's HLO cost analysis counts while-loop bodies ONCE regardless of
    trip count (verified empirically — see EXPERIMENTS.md §Methodology),
    which would silently undercount FLOPs/bytes/collectives of scanned
    layer stacks and chunked attention by the trip count. The dry-run
    therefore compiles small-depth *fully unrolled* cost variants (depth
    1 and 2) with this flag on and extrapolates exactly; production
    compiles keep scans rolled (compile time, memory). Use the
    :func:`unroll_for_cost` context manager, never the list directly.
"""
from __future__ import annotations

import contextlib

UNROLL_FOR_COST = [False]


def cost_unroll(length: int) -> int:
    """Scan unroll factor under the cost-measurement flag."""
    return length if UNROLL_FOR_COST[0] else 1


@contextlib.contextmanager
def unroll_for_cost():
    UNROLL_FOR_COST[0] = True
    try:
        yield
    finally:
        UNROLL_FOR_COST[0] = False
