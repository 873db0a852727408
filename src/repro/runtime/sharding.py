"""Mesh-aware sharding helpers.

Logical-to-physical convention (DESIGN.md §4):

  * ``pod``   — inter-pod axis: data parallelism / pipeline stages only.
  * ``data``  — intra-pod data parallelism (batch).
  * ``model`` — tensor/expert parallelism (heads, ffn, vocab, experts).

Model code calls :func:`shard` with axis names that may or may not exist in
the active mesh; names absent from the mesh are dropped, and with no active
mesh the call is the identity. This keeps one model definition valid on a
single CPU device (smoke tests), the 16x16 single pod, and the 2x16x16
multi-pod mesh.
"""
from __future__ import annotations

import jax
from jax._src import mesh as _mesh_lib
from jax.sharding import PartitionSpec as P
from jax.sharding import get_abstract_mesh

AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"
# logical 'batch' axes; pure-DP strategy extends this with 'model' (§Perf:
# small archs waste the mesh on TP — batch takes the whole machine instead)
_BATCH_AXES = [(AXIS_POD, AXIS_DATA)]


def set_batch_axes(axes: tuple[str, ...]) -> None:
    _BATCH_AXES[0] = tuple(axes)


def batch_axes() -> tuple[str, ...]:
    return _BATCH_AXES[0]


def active_axes() -> tuple[str, ...]:
    mesh = get_abstract_mesh()
    return tuple(mesh.axis_names) if mesh is not None and not mesh.empty else ()


def resolve(*dims, shape: tuple[int, ...] | None = None) -> P:
    """Build a PartitionSpec keeping only axes present in the active mesh.

    Each dim is None, an axis name, or a tuple of axis names ('batch' maps
    to the surviving subset of BATCH_AXES). When ``shape`` is given, axes
    whose mesh extent does not divide the dim size are dropped (e.g. 8 KV
    heads or vocab 50280 on a 16-way model axis -> replicated), so one model
    definition stays valid across meshes and architectures.
    """
    mesh = get_abstract_mesh()
    axes = active_axes()
    used: set[str] = set()        # a mesh axis may shard at most one dim

    def one(i, d):
        if d is None:
            return None
        if d == "batch":
            d = batch_axes()
        if isinstance(d, str):
            d = (d,)
        keep = []
        extent = 1
        for a in d:
            if a not in axes or a in used:
                continue
            if shape is not None:
                if shape[i] % (extent * mesh.shape[a]) != 0:
                    continue
            keep.append(a)
            used.add(a)
            extent *= mesh.shape[a]
        if not keep:
            return None
        return keep[0] if len(keep) == 1 else tuple(keep)

    return P(*(one(i, d) for i, d in enumerate(dims)))


def shard(x: jax.Array, *dims) -> jax.Array:
    """with_sharding_constraint that degrades to identity off-mesh and
    silently replicates non-divisible dims."""
    if not active_axes():
        return x
    return jax.lax.with_sharding_constraint(
        x, resolve(*dims, shape=tuple(x.shape)))


def axis_size(name: str) -> int:
    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


# ---------------------------------------------------------------------------
# Block-key sharding: the axes the OCTENT octree table partitions over
# ---------------------------------------------------------------------------

#: axes eligible to hold a block-key range of the octree table. ``pod``
#: stays a pure data-parallel/pipeline axis (DESIGN.md §4): block keys are
#: batch-tagged Morton codes, maps never cross batch items, so everything
#: *inside* a pod — data and model parallel alike — can serve table shards.
SHARD_AXES = (AXIS_DATA, AXIS_MODEL)


def blockkey_axes(mesh=None) -> tuple[str, ...]:
    """Mesh axes the sorted block directory shards over: every data/model
    axis present in ``mesh`` (default: the active mesh)."""
    if mesh is None:
        mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return ()
    return tuple(a for a in SHARD_AXES if a in mesh.axis_names)


def blockkey_shards(mesh=None) -> int:
    """Number of contiguous block-key ranges the octree table splits into
    (the product of the blockkey axes' extents); 1 off-mesh."""
    if mesh is None:
        mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return 1
    n = 1
    for a in blockkey_axes(mesh):
        n *= int(mesh.shape[a])
    return n


def mesh_fingerprint(mesh=None) -> tuple:
    """Hashable signature of the active mesh — () off-mesh.

    Part of every PlanCache key: a plan built for one mesh carries that
    mesh's sharded search structure (and the devices its arrays are
    committed to), so the same coordinate arrays under a different mesh
    must miss and rebuild. (axis, extent) pairs alone are not enough —
    two same-shape meshes over different device subsets would replay a
    plan pinned to the wrong chips — so the fingerprint also carries the
    device ids backing the mesh (recovered from the context's concrete
    mesh when the active mesh is abstract; see
    :func:`concrete_device_ids`).
    """
    if mesh is None:
        mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return ()
    fp = tuple((a, int(mesh.shape[a])) for a in mesh.axis_names)
    ids = concrete_device_ids(mesh)
    if ids:
        fp += (ids,)
    return fp


def concrete_mesh(mesh=None):
    """The physical :class:`jax.sharding.Mesh` behind ``mesh`` (or the
    active mesh); None off-mesh.

    An AbstractMesh (what ``jax.sharding.get_abstract_mesh`` returns under
    ``jax.set_mesh``) carries no devices, so the concrete mesh that
    ``jax.set_mesh`` installed for this context stands in — read through
    jax's mesh module, because the public ``jax.sharding.get_mesh``
    refuses to run inside ``jax.jit``.
    """
    if not isinstance(mesh, jax.sharding.Mesh):
        mesh = _mesh_lib.get_concrete_mesh()
    return None if mesh is None or mesh.empty else mesh


def concrete_device_ids(mesh=None) -> tuple:
    """Device ids backing ``mesh`` (or the active mesh); () off-mesh.
    Without them, two same-shape meshes over different device subsets
    would be indistinguishable to callers keying caches on the mesh."""
    mesh = concrete_mesh(mesh)
    return () if mesh is None else tuple(
        int(i) for i in mesh.device_ids.ravel())
