"""Sort-free stable ordering of bounded integer keys (Morton-radix binning).

OCTENT's keys are all *bounded composites* — block Morton codes
(3*grid_bits + batch_bits bits), 12-bit local octree codes, (block, tap)
group ids — so the global ``argsort``s the plan build used to lean on are
overkill: a stable counting sort reproduces the exact same permutation
from bincount + prefix-sum passes, with no XLA ``sort`` primitive anywhere
in the jaxpr. That matters on TPU because ``sort`` lowers to a bitonic
network over the full key stream (O(n log^2 n) compare-exchange cycles),
while each counting pass is one one-hot cumsum and two permutation
scatters, and it matters to this repo because the acceptance contract of
the sort-free plan build is jaxpr-auditable (:func:`sort_op_count`).
Those scatters touch O(n) elements but do not cost O(n) HBM time on a
TPU: with colliding or unordered indices they run close to one element
at a time. A pass that can place its elements by reading should
(the tile build does, kernels/spconv_gemm/ops.py; DESIGN.md §5).

Two entry points:

  * :func:`counting_argsort`  — stable ascending argsort of one bounded
    key array, LSD radix over ``digit_bits``-wide digits.
  * :func:`counting_lexsort`  — stable lexicographic argsort over several
    bounded key arrays (minor key first, matching ``jnp.lexsort``), by
    running the radix passes of each key in sequence.

Both return the identical permutation a stable ``jnp.argsort`` /
``jnp.lexsort`` would (tests assert bit-exactness), so they are drop-in
replacements wherever the keys are bounded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _radix_passes(order: jnp.ndarray, cur: jnp.ndarray, nbits: int,
                  digit_bits: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Run the LSD counting passes of one key; returns (order, permuted key).

    ``cur`` must already be permuted by ``order`` (i.e. cur = key[order] for
    the accumulated permutation) and every value must fit ``nbits`` bits.
    """
    n = cur.shape[0]
    nb = 1 << digit_bits
    bins = jnp.arange(nb, dtype=jnp.int32)
    for shift in range(0, nbits, digit_bits):
        d = (cur >> shift) & (nb - 1)
        oh = (d[:, None] == bins[None, :]).astype(jnp.int32)     # (n, nb)
        # stable rank within digit: inclusive prefix count at own position
        within = (jnp.cumsum(oh, axis=0) * oh).sum(axis=1) - 1
        counts = oh.sum(axis=0)
        starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(counts)[:-1].astype(jnp.int32)])
        pos = jnp.take(starts, d) + within
        cur = jnp.zeros_like(cur).at[pos].set(cur)
        order = jnp.zeros((n,), jnp.int32).at[pos].set(order)
    return order, cur


def counting_argsort(keys: jnp.ndarray, nbits: int, *,
                     digit_bits: int = 4) -> jnp.ndarray:
    """Stable ascending argsort of int32 ``keys`` in [0, 2**nbits).

    Bit-identical to ``jnp.argsort(keys, stable=True)`` for in-range keys
    (property-tested), with zero ``sort`` primitives in the jaxpr. ``nbits``
    must be static; keys outside the range silently misplace, so callers
    map their invalid sentinel to ``1 << nbits`` and pass ``nbits + 1``.
    """
    assert nbits <= 31, nbits
    n = keys.shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    order, _ = _radix_passes(order, keys.astype(jnp.int32), nbits, digit_bits)
    return order


def counting_lexsort(keys: tuple[jnp.ndarray, ...], nbits: tuple[int, ...],
                     *, digit_bits: int = 4) -> jnp.ndarray:
    """Stable lexicographic argsort, minor key first (= ``jnp.lexsort``).

    ``keys[i]`` must lie in [0, 2**nbits[i]); the last key is the primary
    one. Equivalent to LSD radix over the concatenated bit budget.
    """
    n = keys[0].shape[0]
    order = jnp.arange(n, dtype=jnp.int32)
    for key, bits in zip(keys, nbits):
        cur = jnp.take(key.astype(jnp.int32), order)
        order, _ = _radix_passes(order, cur, bits, digit_bits)
    return order


def rank_from_order(order: jnp.ndarray) -> jnp.ndarray:
    """Inverse permutation: rank[i] = sorted position of element i."""
    n = order.shape[0]
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# jaxpr audit — the acceptance check of the sort-free contract
# ---------------------------------------------------------------------------

def _walk_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            if hasattr(v, "eqns"):
                yield from _walk_jaxprs(v)
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield from _walk_jaxprs(v.jaxpr)


def sort_op_count(fn, *args) -> int:
    """Number of XLA ``sort`` primitives anywhere in ``fn``'s jaxpr.

    The sort-free plan build must show 0 here (tests + CI smoke); the
    retained argsort baselines must show > 0, proving the audit bites.
    """
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return sum(eqn.primitive.name == "sort"
               for jpr in _walk_jaxprs(jaxpr) for eqn in jpr.eqns)


def avals_with_shape(fn, *args, shape: tuple[int, ...]) -> int:
    """Number of op outputs with exactly ``shape`` in ``fn``'s jaxpr —
    used to audit that the fused query path never materializes the
    (N, K, 3) query tensor in HBM."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return sum(tuple(getattr(ov.aval, "shape", ())) == tuple(shape)
               for jpr in _walk_jaxprs(jaxpr) for eqn in jpr.eqns
               for ov in eqn.outvars)


def shard_body_avals_with_shape(fn, *args, shape: tuple[int, ...]) -> int:
    """Number of values (inputs and op outputs) with exactly ``shape``
    inside the shard_map bodies of ``fn``'s jaxpr.

    The per-device audit of the sharded OCTENT search: the mapped region
    must only ever hold (n_pad/S,)-shaped table slices, so counting
    full-table (n_pad,) avals here must give 0 — while counting the
    slice shape gives > 0, proving the audit looks inside the body.
    """
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    hits = 0
    for jpr in _walk_jaxprs(jaxpr):
        for eqn in jpr.eqns:
            if eqn.primitive.name != "shard_map":
                continue
            body = eqn.params["jaxpr"]
            body = getattr(body, "jaxpr", body)      # ClosedJaxpr on new jax
            for inner in _walk_jaxprs(body):
                inner = getattr(inner, "jaxpr", inner)   # unwrap ClosedJaxpr
                hits += sum(
                    tuple(getattr(v.aval, "shape", ())) == tuple(shape)
                    for v in inner.invars)
                hits += sum(
                    tuple(getattr(ov.aval, "shape", ())) == tuple(shape)
                    for e in inner.eqns for ov in e.outvars)
    return hits
