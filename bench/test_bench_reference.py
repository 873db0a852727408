"""The plain reference against the program's own ``ref`` forward on a
tiny cloud, both through the family's hooks, and the control's
precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import scenes

TRAFFIC = {"generator": "indoor",
           "params": {"n_points": 3000, "room_min_m": 2.2, "room_max_m": 2.3,
                      "height_m": 1.5, "voxel_m": 0.05},
           "extent_voxels": [48, 48, 32]}


@pytest.fixture(scope="module")
def spec():
    return run.cell_spec(run.ROOT, "scannet.fresh-c4")


def program_logits(spec, a, params, coords, feats, rows):
    """The answer of the family's served path, on the ``ref`` backend."""
    fam = spec["family"]
    served = fam.serve(a, spec["config"], params, bucket=rows, clients=1,
                       impl="ref")
    engine = served["engine"]
    engine.submit("r", *scenes.padded(coords, feats, rows), deadline_s=600.0)
    (res,) = engine.step()
    assert res.status == "completed", res
    return np.asarray(fam.answer(res))[:coords.shape[0]]


@pytest.mark.parametrize("arch", [
    reference.Arch(4, 8, (8, 16), (16, 8), 1, 4),
    reference.Arch(4, 8, (8, 16, 16), (16, 8, 8), 2, 5),
], ids=["two-stage", "three-stage"])
def test_reference_agrees_with_program_ref_forward(spec, arch):
    fam = spec["family"]
    coords, feats = scenes.base_scene(TRAFFIC, 3)
    moved = scenes.transform(coords, np.asarray(TRAFFIC["extent_voxels"]),
                             5, (2, 1, 4))
    rows = 4096
    assert moved.shape[0] <= rows
    params = fam.init_params(arch, 2**33 + 17)
    want = fam.reference(arch, params, moved, feats, rows,
                         precision="highest")
    got = program_logits(spec, arch, params, moved, feats, rows)
    assert np.abs(want).max() > 0.1
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-5, err


def test_bf16x3_is_the_next_precision_down():
    k = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(k[0], (256, 384), jnp.float32)
    w = jax.random.normal(k[1], (384, 256), jnp.float32)
    exact = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    hi = np.asarray(reference.dot_highest(x, w), np.float64)
    lo = np.asarray(reference.dot_bf16x3(x, w), np.float64)
    scale = np.abs(exact).max()
    e_hi = np.abs(hi - exact).max() / scale
    e_lo = np.abs(lo - exact).max() / scale
    assert e_lo > 4 * e_hi, (e_lo, e_hi)
    assert 1e-7 < e_lo < 2.0 ** -12, e_lo
    # bf16-representable operands lose nothing in three passes
    xb = x.astype(jnp.bfloat16).astype(jnp.float32)
    wb = w.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(reference.dot_bf16x3(xb, wb)),
                               np.asarray(reference.dot_highest(xb, wb)),
                               rtol=1e-5, atol=1e-4)
