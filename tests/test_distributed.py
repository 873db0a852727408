"""Multi-device integration tests (8 host devices via the shared
tests/proptest.run_script subprocess harness — the XLA device-count flag
must be set before jax initializes, and the main test process must keep
seeing 1 device per the brief)."""
import pytest

from tests.proptest import run_script


def test_pipeline_matches_sequential():
    out = run_script("""
import numpy as np, jax, jax.numpy as jnp
from jax import make_mesh, set_mesh
from jax.sharding import AxisType
from repro.runtime.pipeline import pipeline_apply, stack_stages

mesh = make_mesh((4, 2), ("pod", "data"),
                 axis_types=(AxisType.Auto,) * 2)
L, D, M, MB = 8, 16, 6, 4
rng = np.random.default_rng(0)
w = jnp.asarray(rng.standard_normal((L, D, D)) * 0.2, jnp.float32)
x = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)

def layer(wl, h):
    return jnp.tanh(h @ wl)

def stage_fn(params, h):
    for i in range(params.shape[0]):
        h = layer(params[i], h)
    return h

stages = stack_stages(w, 4)
with set_mesh(mesh):
    got = pipeline_apply(stages, x, stage_fn, mesh=mesh, axis="pod")
ref = x
for i in range(L):
    ref = layer(w[i], ref)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
print("PIPELINE_OK")
""")
    assert "PIPELINE_OK" in out


def test_compressed_psum_close_to_exact():
    out = run_script("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.runtime.compress import compressed_psum_mean
from jax import make_mesh, set_mesh, shard_map
from jax.sharding import AxisType

mesh = make_mesh((8,), ("pod",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(1)
g = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)

def f(x):
    return compressed_psum_mean(x[0], "pod")

fn = shard_map(f, mesh=mesh, in_specs=(P("pod"),), out_specs=P(),
               check_vma=False)
with set_mesh(mesh):
    got = fn(g)
exact = np.asarray(g).mean(0)
err = np.abs(np.asarray(got) - exact).max()
scale = np.abs(np.asarray(g)).max() / 127
assert err <= scale + 1e-6, (err, scale)
print("COMPRESS_OK", err)
""")
    assert "COMPRESS_OK" in out


def test_sharded_train_step_matches_single_device():
    """The same reduced model + batch must produce identical loss on a
    (2, 4) mesh and on one device — sharding is semantics-preserving."""
    out = run_script("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch import shardings
from jax import set_mesh
from repro.launch.mesh import make_test_mesh
from repro.launch.train import make_train_step, init_state
from repro.models import api
from repro.optim import adamw
from repro.data.tokens import TokenStream

cfg = get_config("qwen3-1.7b").reduced()
model = api.build_model(cfg)
state = init_state(model)
stream = TokenStream(vocab=cfg.vocab, batch=8, seq=32, seed=0)
batch = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}
step = make_train_step(model, adamw.AdamWConfig())

ref_state, ref_metrics = jax.jit(step)(state, batch)

mesh = make_test_mesh(2, 4)
params_abs = jax.eval_shape(lambda: state[0])
opt_abs = jax.eval_shape(lambda: state[1])
p_sh = shardings.param_shardings(params_abs, mesh)
o_sh = shardings.opt_state_shardings(opt_abs, mesh)
b_sh = shardings.batch_shardings(
    jax.eval_shape(lambda: batch), mesh)
with set_mesh(mesh):
    fn = jax.jit(step, in_shardings=((p_sh, o_sh), b_sh),
                 out_shardings=((p_sh, o_sh), None))
    new_state, metrics = fn(state, batch)
np.testing.assert_allclose(float(metrics["loss"]),
                           float(ref_metrics["loss"]), rtol=2e-3)
# params updated identically (up to bf16-free f32 numerics)
for a, b in zip(jax.tree.leaves(ref_state[0]), jax.tree.leaves(new_state[0])):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=3e-2,
                               atol=3e-2)
print("SHARDED_OK", float(metrics["loss"]))
""")
    assert "SHARDED_OK" in out


@pytest.mark.slow
def test_dryrun_cell_on_test_mesh():
    """One full dry-run cell on 8 devices (fast proxy for the 512-dev run)."""
    out = run_script("""
import numpy as np, jax
from repro.configs import get_config, SHAPE_CELLS
from repro.launch.mesh import make_test_mesh
from jax import set_mesh
from repro.launch import shardings
from repro.launch.dryrun import build_cell
from repro.models import api

cfg = get_config("tinyllama-1.1b").reduced()
model = api.build_model(cfg)
cell = SHAPE_CELLS["train_4k"]
import dataclasses
cell = dataclasses.replace(cell, seq_len=64, global_batch=8)
mesh = make_test_mesh(2, 4)
fn, args, in_sh, out_sh, _donate = build_cell(model, cell, mesh)
with set_mesh(mesh):
    compiled = jax.jit(fn, in_shardings=in_sh,
                       out_shardings=out_sh).lower(*args).compile()
ca = compiled.cost_analysis()
ca = ca[0] if isinstance(ca, (list, tuple)) else ca   # dict on new jax
print("DRYRUN_OK", ca.get("flops"))
""")
    assert "DRYRUN_OK" in out
