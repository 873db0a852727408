"""Pallas TPU kernel: fused OCTENT map-search query (paper Fig. 5(c) l.7-13).

The XLA builder (`mapsearch.build_kmap_octree`) materializes the full
(N, K, 3) query tensor plus broadcast batch/valid arrays in HBM, then runs
`searchsorted` and the banked-table gather as separate HBM-roundtripping
ops. This kernel is the Query Transmitter of Fig. 6(a) as one pass: each
grid step pulls a ``bq``-voxel tile of packed coordinates into VMEM,
generates all K offset queries **in-register** (broadcast adds over the
static offset list), Morton-encodes them with the same shift/mask ladder
the ASIC wires into PNELUT, and resolves them against the SMEM-resident
block directory + compacted banked table with two windowed scans (below).
The kmap tile is written straight to the output block — no
query tensor, no bkey array, no searchsorted intermediate ever exists in
HBM (jaxpr-audited in tests/test_mapsearch.py).

Table layout (built sort-free by kernels/octent/ops.build_query_table):

  * ``ublocks`` (max_blocks,)  — sorted occupied block keys, the octree
    directory. First search: block key -> block rank.
  * ``tkey``    (n_pad,)       — sorted compacted table addresses
    ``rank * 4096 + bank * 512 + row`` — exactly the flat address space of
    the paper's 8-bank SRAM (Fig. 6(a)), minus the empty slots, so the
    second search lands on the same (bank, row) cell the ASIC's parallel
    banks would strobe. ``tval`` holds the voxel index per slot.

Searching the *compacted* table instead of direct-addressing the dense
(max_blocks * 4096) one trades a search for a table that actually fits
on chip (4N bytes vs 16 KiB per block) — the dense table stays
the XLA oracle's representation.

Mosaic lowers no per-lane dynamic gather out of a VMEM vector, so the
two lookups never index the tables with a query tile. The tables sit in
SMEM (scalar-prefetched) and each lookup is a compare-and-select scan:
a scalar binary search narrows the table to the window of keys the
tile's live queries can hit, ``[lower_bound(kmin), upper_bound(kmax))``,
and every entry of that window is broadcast against the whole (K, bq)
query tile on the VPU. The scan runs from the top of the window down, so
under duplicate keys the lowest matching slot wins — exactly the
``searchsorted`` lower bound of the oracle (ref.py), hence bit-identical
kmaps. SMEM (1 MiB on v5e) holds the whole directory and table, 12 bytes
per slot when ``max_blocks`` equals the voxel count: that caps one
search at about 85k voxels (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import morton

#: lane width of the table arrays (tkey/tval/ublocks are padded to this)
LANE = 128


def _lower_bound(arr_ref, key, lo, hi, steps: int):
    """First ``j`` in ``[lo, hi)`` with ``arr_ref[j] >= key`` (``hi`` if
    none): a scalar binary search over a sorted SMEM array, ``steps``
    fixed iterations (enough for the array's length)."""
    def step(_, c):
        lo, hi = c
        mid = (lo + hi) // 2
        right = (lo < hi) & (arr_ref[jnp.minimum(mid, hi - 1)] < key)
        return (jnp.where(right, mid + 1, lo),
                jnp.where((lo < hi) & ~right, mid, hi))
    return jax.lax.fori_loop(0, steps, step, (lo, hi))[0]


def _match(key_ref, val_ref, keys, live, lo, hi, steps: int):
    """Per query of the tile, the value of the first table slot whose key
    equals it (-1 if none) — the compare-and-select scan of the module
    doc. ``val_ref`` None yields the slot index itself. Only ``live``
    queries narrow the scanned window; the others may still match inside
    it and are masked by the caller."""
    big = jnp.iinfo(jnp.int32).max
    kmin = jnp.min(jnp.where(live, keys, big))
    kmax = jnp.max(jnp.where(live, keys, -1))
    w_lo = _lower_bound(key_ref, kmin, lo, hi, steps)
    w_hi = _lower_bound(key_ref, kmax + 1, w_lo, hi, steps)

    def scan(t, acc):
        j = w_hi - 1 - t
        val = j if val_ref is None else val_ref[j]
        return jnp.where(keys == key_ref[j], val, acc)

    return jax.lax.fori_loop(0, w_hi - w_lo, scan,
                             jnp.full(keys.shape, -1, jnp.int32))


def _octent_kernel(nblk_ref, ub_ref, tkey_ref, tval_ref, q_ref, offs_ref,
                   out_ref, *, grid_bits: int, max_blocks: int, n_t: int):
    n_blocks = jnp.minimum(nblk_ref[0], max_blocks)

    # -- query generation, in-register: (K, bq) per coordinate channel
    x = q_ref[0:1, :] + offs_ref[:, 0:1]
    y = q_ref[1:2, :] + offs_ref[:, 1:2]
    z = q_ref[2:3, :] + offs_ref[:, 2:3]
    bt = q_ref[3:4, :]
    v = q_ref[4:5, :] != 0

    limit = (1 << grid_bits) * morton.BLOCK_SIZE
    inb = ((x >= 0) & (x < limit) & (y >= 0) & (y < limit)
           & (z >= 0) & (z < limit) & v)
    cx = jnp.clip(x, 0, limit - 1)
    cy = jnp.clip(y, 0, limit - 1)
    cz = jnp.clip(z, 0, limit - 1)

    # -- octree encoding (eq. 3), the PNELUT shift/mask ladder on the VPU
    bkey = (morton.interleave_xyz(cx >> morton.BLOCK_BITS,
                                  cy >> morton.BLOCK_BITS,
                                  cz >> morton.BLOCK_BITS, grid_bits)
            | (bt << (3 * grid_bits)))
    phi = morton.interleave_xyz(cx & (morton.BLOCK_SIZE - 1),
                                cy & (morton.BLOCK_SIZE - 1),
                                cz & (morton.BLOCK_SIZE - 1),
                                morton.BLOCK_BITS)
    bank, row = morton.bank_and_row(phi)

    # -- stage 1: block key -> rank in the directory
    rank = _match(ub_ref, None, bkey, inb, 0, n_blocks,
                  max(max_blocks.bit_length(), 1))
    hit_b = inb & (rank >= 0)

    # -- stage 2: (rank, bank, row) -> voxel via the compacted banked table
    key2 = rank * morton.TABLE_SIZE + bank * morton.BANK_ROWS + row
    val = _match(tkey_ref, tval_ref, key2, hit_b, 0, n_t,
                 max(n_t.bit_length(), 1))
    out_ref[...] = jnp.where(hit_b, val, -1)


@functools.partial(jax.jit, static_argnames=("grid_bits", "batch_bits", "bq",
                                             "interpret"))
def octent_query(qpack: jnp.ndarray, offsets: jnp.ndarray,
                 ublocks: jnp.ndarray, tkey: jnp.ndarray, tval: jnp.ndarray,
                 n_blocks: jnp.ndarray, *, grid_bits: int = 7,
                 batch_bits: int = 4, bq: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Fused query over a packed voxel stream. Returns (K, N_pad) int32.

    qpack (5, N_pad): rows x, y, z, batch, valid — N_pad a bq multiple.
    offsets (K, 3); ublocks/tkey/tval from ops.build_query_table (tkey and
    tval LANE-padded, ublocks INVALID-padded); n_blocks () or (1,).
    """
    five, n_pad = qpack.shape
    assert five == 5 and n_pad % bq == 0, (qpack.shape, bq)
    k = offsets.shape[0]
    max_blocks = ublocks.shape[0]
    n_t = tkey.shape[0]
    assert n_t % LANE == 0 and tval.shape[0] == n_t, (n_t, tval.shape)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_pad // bq,),
        in_specs=[
            pl.BlockSpec((5, bq), lambda i, *pf: (0, i)),
            pl.BlockSpec((k, 3), lambda i, *pf: (0, 0)),
        ],
        out_specs=pl.BlockSpec((k, bq), lambda i, *pf: (0, i)),
    )
    kernel = functools.partial(_octent_kernel, grid_bits=grid_bits,
                               max_blocks=max_blocks, n_t=n_t)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, n_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="octent_query",
    )(jnp.atleast_1d(n_blocks).astype(jnp.int32), ublocks, tkey, tval,
      qpack, offsets)
