"""Sharded OCTENT map search: key-range-partitioned QueryTable on a mesh.

The acceptance contract (DESIGN.md §9): ``build_kmap(impl='sharded')`` is
bit-identical to the single-device engine on every mesh shape, the mapped
region only ever holds per-shard table slices (jaxpr audit), both query
stages are answered by the shard owning the key range (routing audit),
and the overflow flag propagates across shards.

In-process tests run on a 1-device mesh (S=1 exercises the shard_map
plumbing and the off-mesh error path); multi-device parity (2/4/8-way,
data x model) runs on 8 host CPU devices via the shared
tests/proptest.run_script subprocess harness.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import set_mesh
from jax.sharding import Mesh

from repro.kernels.octent import ops as oct_ops
from repro.runtime import sharding
from tests.proptest import forall, random_cloud, run_script


def _one_device_mesh(names=("data",)):
    shape = (1,) * len(names)
    return Mesh(np.array(jax.devices()[:1]).reshape(shape), names)


# ---------------------------------------------------------------------------
# In-process: axis helpers, S=1 plumbing, error paths
# ---------------------------------------------------------------------------

def test_blockkey_axis_helpers():
    assert sharding.blockkey_axes() == ()
    assert sharding.blockkey_shards() == 1
    assert sharding.mesh_fingerprint() == ()
    dev_ids = (jax.devices()[0].id,)
    with set_mesh(_one_device_mesh(("data",))):
        assert sharding.blockkey_axes() == ("data",)
        assert sharding.blockkey_shards() == 1
        # physical meshes fingerprint by shape AND device identity
        assert sharding.mesh_fingerprint() == (("data", 1), dev_ids)
    with set_mesh(_one_device_mesh(("pod", "model"))):
        # pod never holds a block-key range (DP/pipeline only)
        assert sharding.blockkey_axes() == ("model",)
        assert sharding.mesh_fingerprint() == (("pod", 1), ("model", 1),
                                               dev_ids)


def test_sharded_requires_mesh_with_blockkey_axes():
    rng = np.random.default_rng(0)
    c, b, v = map(jnp.asarray, random_cloud(rng, 32, extent=20, batch=1))
    with pytest.raises(ValueError, match="mesh"):
        oct_ops.build_kmap(c, b, v, max_blocks=32, impl="sharded")
    with set_mesh(_one_device_mesh(("pod",))):
        with pytest.raises(ValueError, match="nothing to partition"):
            oct_ops.build_kmap(c, b, v, max_blocks=32, impl="sharded")


@forall(6)
def test_sharded_matches_ref_on_one_device_mesh(rng):
    """S=1 runs the full shard_map machinery against the single-device
    oracle in-process, including out-of-grid neighbors at the grid limit."""
    n = int(rng.integers(24, 64))
    origin = int(rng.choice([0, 2048 - 12]))
    c, b, v = map(jnp.asarray, random_cloud(rng, n, extent=12, batch=2,
                                            origin=origin))
    km_ref, nb_ref = oct_ops.build_kmap(c, b, v, max_blocks=n, impl="ref")
    with set_mesh(_one_device_mesh(("data",))):
        km, nb = oct_ops.build_kmap(c, b, v, max_blocks=n, impl="sharded")
    np.testing.assert_array_equal(np.asarray(km), np.asarray(km_ref))
    assert int(nb) == int(nb_ref)


def test_search_impl_auto_stays_single_device_on_trivial_mesh():
    # a 1-way mesh has nothing to shard: auto keeps the local engine
    with set_mesh(_one_device_mesh(("data",))):
        assert oct_ops.search_impl() in ("ref", "pallas")


# ---------------------------------------------------------------------------
# Multi-device: parity, empty shards, audits, overflow (subprocess, 8 dev)
# ---------------------------------------------------------------------------

def test_sharded_parity_multiway():
    """Randomized parity vs the single-device build_kmap across 2/4/8-way
    and data x model meshes, including empty shards (a clustered cloud
    occupying one block leaves S-1 key ranges empty), all-invalid tiles,
    and out-of-grid queries at the grid limit."""
    out = run_script("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.kernels.octent import ops as oct_ops
from jax import set_mesh
from tests.proptest import random_cloud

n = 120            # fixed size so each mesh's lowering caches across cases
clouds = []
for seed in range(2):
    rng = np.random.default_rng(seed)
    clouds += [
        ("uniform", random_cloud(rng, n, extent=40, batch=2)),
        ("grid_limit", random_cloud(rng, n, extent=16, batch=2,
                                    origin=2048 - 16)),
        ("one_block", random_cloud(rng, n, extent=14, batch=1)),
        ("all_invalid", random_cloud(rng, n, extent=30, batch=2, n_valid=0)),
    ]
meshes = [((2,), ("data",), 2), ((4,), ("model",), 4),
          ((8,), ("data",), 8), ((2, 4), ("data", "model"), 8)]
refs = []
for case, cloud in clouds:
    c, b, v = map(jnp.asarray, cloud)
    refs.append((case, c, b, v) + oct_ops.build_kmap(c, b, v, max_blocks=n,
                                                     impl="ref"))
for shape, names, nd in meshes:
    mesh = Mesh(np.array(jax.devices()[:nd]).reshape(shape), names)
    with set_mesh(mesh):
        assert oct_ops.search_impl() == "sharded"
        for case, c, b, v, km_ref, nb_ref in refs:
            km, nb = oct_ops.build_kmap(c, b, v, max_blocks=n,
                                        impl="sharded")
            np.testing.assert_array_equal(np.asarray(km), np.asarray(km_ref),
                                          err_msg=f"{case} {shape} {names}")
            assert int(nb) == int(nb_ref)
print("SHARDED_PARITY_OK")
""", timeout=900)
    assert "SHARDED_PARITY_OK" in out


def test_sharded_audit_routing_and_overflow():
    """(a) jaxpr audit: the shard_map body holds only (n_pad/S,) table
    slices, never the full (n_pad,) voxel table; (b) routing audit: each
    stage's answer comes from the shard owning the key range (a single
    lower-bound against the boundary keys); (c) the overflow flag
    reaches ConvPlan.overflow under jit on the mesh."""
    out = run_script("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import binning, morton, plan as planlib
from repro.kernels.octent import ops as oct_ops, sharded
from jax import set_mesh
from tests.proptest import random_cloud

rng = np.random.default_rng(0)
mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))
# N=200 pads the table to 512 slots -> 128-slot slices; the audit shapes
# are distinct from every replicated per-voxel (200,) array in the body
c, b, v = map(jnp.asarray, random_cloud(rng, 200, extent=40, batch=2))
offs = jnp.asarray(morton.subm3_offsets())
with set_mesh(mesh):
    fn = lambda c, b, v: sharded.build_kmap_sharded(c, b, v, max_blocks=200)[0]
    assert binning.shard_body_avals_with_shape(fn, c, b, v, shape=(512,)) == 0
    assert binning.shard_body_avals_with_shape(fn, c, b, v, shape=(128,)) > 0

    sqt = sharded.build_query_table_sharded(c, b, v, max_blocks=200)
    km, nb, pranks, partials = sharded.octent_query_sharded(
        c, b, v, offs, sqt, return_partials=True)
pr, p, km_np = np.asarray(pranks), np.asarray(partials), np.asarray(km)
hit = km_np >= 0
assert ((p >= 0).sum(0) == hit.astype(int)).all()    # exactly one answerer
assert ((pr >= 0).sum(0) <= 1).all()
qc = np.clip(np.asarray(c)[:, None, :] + np.asarray(offs)[None, :, :],
             0, 2047)
bb = jnp.asarray(np.broadcast_to(np.asarray(b)[:, None], qc.shape[:2]))
bk = np.asarray(morton.block_key(jnp.asarray(qc), bb))
own1 = np.asarray(sharded.owner_shard(sqt.bounds, jnp.asarray(bk)))
dir_hit = (pr >= 0).any(0)
assert (np.argmax(pr >= 0, 0)[dir_hit] == own1[dir_hit]).all()
rank = pr.max(0)
bank, row = morton.bank_and_row(morton.local_code(jnp.asarray(qc)))
key2 = rank * morton.TABLE_SIZE + np.asarray(bank) * morton.BANK_ROWS \
    + np.asarray(row)
own2 = np.asarray(sharded.owner_shard(sqt.tbounds, jnp.asarray(key2)))
assert (np.argmax(p >= 0, 0)[hit] == own2[hit]).all()

with set_mesh(mesh):
    flag = jax.jit(lambda c, b, v: planlib.subm3_plan(
        c, b, v, max_blocks=2, bm=8, search_impl="sharded").overflow)(c, b, v)
    ok = jax.jit(lambda c, b, v: planlib.subm3_plan(
        c, b, v, max_blocks=200, bm=8, search_impl="sharded").overflow)(c, b, v)
assert bool(flag) and not bool(ok)

# same-shape meshes over different device subsets must MISS: a plan pins
# its sharded tables to specific chips, so the fingerprint carries ids
cache = planlib.PlanCache()
mesh_a = Mesh(np.array(jax.devices()[:2]).reshape(2), ("data",))
mesh_b = Mesh(np.array(jax.devices()[2:4]).reshape(2), ("data",))
with set_mesh(mesh_a):
    pa = planlib.subm3_plan(c, b, v, max_blocks=200, bm=8,
                            search_impl="ref", cache=cache)
with set_mesh(mesh_b):
    pb = planlib.subm3_plan(c, b, v, max_blocks=200, bm=8,
                            search_impl="ref", cache=cache)
assert pb is not pa and cache.misses == 2 and cache.hits == 0
print("SHARDED_AUDIT_OK")
""")
    assert "SHARDED_AUDIT_OK" in out


def test_sharded_minkunet_and_vjp():
    """MinkUNet multi-cloud inference under a (2, 4) mesh: per-cloud plans
    (map search stays flat per cloud across enc/dec stage reuse), sharded
    search end-to-end parity vs the meshless model, and gradients through
    execute on a sharded plan matching the single-device gradients."""
    out = run_script("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import plan as planlib, spconv
from repro.core.spconv import SparseTensor
from repro.data import pointcloud
from repro.models import minkunet
from jax import set_mesh
from tests.proptest import random_cloud

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
cfg = minkunet.MinkUNetConfig(stem=8, enc=(8, 16), dec=(16, 8), classes=4,
                              blocks=2)
params = minkunet.init_model(cfg, jax.random.key(0))
rng = np.random.default_rng(2)
clouds = []
for i in range(2):
    vb = pointcloud.make_batch(rng, "indoor", batch_size=1, max_voxels=128)
    clouds.append(SparseTensor(jnp.asarray(vb.coords), jnp.asarray(vb.batch),
                               jnp.asarray(vb.valid), jnp.asarray(vb.feats)))

refs = [minkunet.forward(params, st, cfg, impl="ref") for st in clouds]
planlib.reset_mapsearch_counter()
with set_mesh(mesh):
    outs = minkunet.forward_multicloud(params, clouds, cfg, impl="ref")
per_cloud = len(cfg.enc) + (len(cfg.enc) + 1)   # gconv2 + Subm3 resolutions
assert planlib.mapsearch_call_count() == per_cloud * len(clouds), \\
    planlib.mapsearch_call_count()
for got, ref in zip(outs, refs):
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
print("MULTICLOUD_OK")

# VJP: grads through execute on a sharded plan == single-device grads
rng = np.random.default_rng(3)
n, cin, cout = 40, 8, 12
c, b, v = map(jnp.asarray, random_cloud(rng, n, extent=14, batch=2))
feats = jnp.asarray(rng.standard_normal((n, cin)), jnp.float32)
w = jnp.asarray(rng.standard_normal((27, cin, cout)) * 0.1, jnp.float32)
bias = jnp.asarray(rng.standard_normal(cout), jnp.float32)
plan_ref = planlib.subm3_plan(c, b, v, max_blocks=n, bm=8,
                              search_impl="ref")
with set_mesh(mesh):
    plan_sh = planlib.subm3_plan(c, b, v, max_blocks=n, bm=8,
                                 search_impl="sharded")
np.testing.assert_array_equal(np.asarray(plan_sh.kmap),
                              np.asarray(plan_ref.kmap))

def loss(plan):
    def f(feats, w, bias):
        out = planlib.execute(plan, feats, w, bias, impl="ref")
        return (out ** 2).sum()
    return f

g_ref = jax.grad(loss(plan_ref), argnums=(0, 1, 2))(feats, w, bias)
g_sh = jax.grad(loss(plan_sh), argnums=(0, 1, 2))(feats, w, bias)
for a, b_ in zip(g_ref, g_sh):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               rtol=1e-5, atol=1e-6)
print("SHARDED_VJP_OK")
""")
    assert "MULTICLOUD_OK" in out and "SHARDED_VJP_OK" in out
