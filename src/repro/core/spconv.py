"""SpConv layers: Subm3 / Gconv3 / Gconv2 / Tconv2 (paper §II-A3, §IV-D).

Functional layers over a padded, mask-carrying :class:`SparseTensor`. The
layer set and naming follows the paper exactly; each layer is map search
(OCTENT) + rulebook execution (SPAC) and is fully jittable with static
shapes.

Execution is plan-based (core/plan.py): each layer builds — or fetches from
a :class:`~repro.core.plan.PlanCache` — a geometry-only ConvPlan (kernel
map + tap-scheduled tiles) and executes it through the gather-fused Pallas
backend by default. ``method`` selects the map-search implementation so the
paper's baselines stay runnable end-to-end, and ``impl='xla'`` routes to
the pure-XLA tap-scan oracle (rulebook.apply_kmap_gather) for parity runs.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import mapsearch, plan as planlib, rulebook


class SparseTensor(NamedTuple):
    """COO sparse tensor (eq. 1) with static row budget + validity mask."""

    coords: jnp.ndarray   # (N, 3) int32 voxel coordinates
    batch: jnp.ndarray    # (N,) int32 batch index
    valid: jnp.ndarray    # (N,) bool
    feats: jnp.ndarray    # (N, C)

    @property
    def n_max(self) -> int:
        return self.coords.shape[0]

    def replace_feats(self, feats: jnp.ndarray) -> "SparseTensor":
        return self._replace(feats=feats)


def mask_feats(st: SparseTensor) -> SparseTensor:
    """Zero features on invalid rows (keeps padding inert through matmuls)."""
    return st.replace_feats(jnp.where(st.valid[:, None], st.feats, 0))


def make_sparse_tensor(coords, batch, valid, feats, *, grid_bits: int = 7,
                       batch_bits: int = 4,
                       policy=None) -> tuple[SparseTensor, "object"]:
    """Sanitizing SparseTensor constructor (DESIGN.md §11 ingress guard).

    Runs :func:`repro.core.validate.sanitize_cloud` over the raw stream
    — non-finite coordinates, out-of-grid voxels, duplicates, dtype
    drift — under the active ``REPRO_GUARD_VALIDATE`` policy (or an
    explicit ``policy``), then wraps the repaired stream. Repairs only
    clear ``valid`` bits / cast dtypes; shapes never change, so the
    tensor is drop-in for the jitted model step. Returns
    ``(tensor, CloudReport)``; a clean cloud passes the original array
    objects through (the PlanCache identity fast path still hits).
    """
    from repro.core import validate
    from repro.runtime import guard
    pol = policy if policy is not None else guard.validate_policy()
    if pol is None:
        return SparseTensor(coords=coords, batch=batch, valid=valid,
                            feats=feats), None
    coords, batch, valid, feats, report = validate.sanitize_cloud(
        coords, batch, valid, feats, grid_bits=grid_bits,
        batch_bits=batch_bits, policy=pol)
    return SparseTensor(coords=coords, batch=batch, valid=valid,
                        feats=feats), report


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_conv(key: jax.Array, k_taps: int, c_in: int, c_out: int,
              dtype=jnp.float32) -> dict:
    fan_in = k_taps * c_in
    w = jax.random.normal(key, (k_taps, c_in, c_out), dtype) * jnp.sqrt(2.0 / fan_in)
    return {"w": w, "b": jnp.zeros((c_out,), dtype)}


def init_batchnorm(c: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype),
            "mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def subm_conv3(st: SparseTensor, params: dict, *, max_blocks: int,
               method: str = "octree", grid_bits: int = 7,
               batch_bits: int = 4, spac: bool = True,
               act: "object | None" = None,
               plan: planlib.ConvPlan | None = None,
               cache: planlib.PlanCache | None = None,
               impl: str | None = None, search_impl: str | None = None,
               bm: int = 128, bo: int | None = None) -> SparseTensor:
    """Submanifold 3x3x3 SpConv (Subm3): coordinates unchanged (Fig. 2).

    Pass ``cache`` to share map search across stacked blocks on the same
    coordinate set, or ``plan`` to reuse an explicit prebuilt plan.
    ``impl`` selects the rulebook-execution backend, ``search_impl`` the
    OCTENT query backend (kernels/octent/ops.search_impl resolves None).
    ``act`` threads the previous layer's epilogue-emitted ActSparsity as
    the SPAC liveness source instead of a fresh row sweep (DESIGN.md §14).
    """
    if plan is None:
        plan = planlib.subm3_plan(st.coords, st.batch, st.valid,
                                  max_blocks=max_blocks, method=method,
                                  grid_bits=grid_bits, batch_bits=batch_bits,
                                  bm=bm, bo=bo, search_impl=search_impl,
                                  cache=cache)
    out = planlib.execute(plan, st.feats, params["w"], params["b"],
                          spac=spac, act=act, impl=impl, src_valid=st.valid)
    out = jnp.where(st.valid[:, None], out, 0)
    return st.replace_feats(out)


def fold_bn_inference(conv_bias: jnp.ndarray | None, bn_params: dict, *,
                      eps: float = 1e-5):
    """Fold conv bias + inference BatchNorm into the fused-epilogue affine.

    ``y = (conv_out + b - mean) * rsqrt(var + eps) * scale + bias`` becomes
    ``y = conv_out * s + t`` with ``s = scale * rsqrt(var + eps)`` and
    ``t = (b - mean) * s + bias`` — exactly :func:`batch_norm` in
    inference mode (same eps, f32 math). Returns ``(s, t)`` float32.
    """
    s = (bn_params["scale"].astype(jnp.float32)
         * jax.lax.rsqrt(bn_params["var"].astype(jnp.float32) + eps))
    b = 0.0 if conv_bias is None else conv_bias.astype(jnp.float32)
    t = (b - bn_params["mean"].astype(jnp.float32)) * s \
        + bn_params["bias"].astype(jnp.float32)
    return s, t


def subm_conv3_bn_relu(st: SparseTensor, conv_params: dict, bn_params: dict,
                       *, max_blocks: int, method: str = "octree",
                       grid_bits: int = 7, batch_bits: int = 4,
                       spac: bool = True, act: "object | None" = None,
                       eps: float = 1e-5,
                       plan: planlib.ConvPlan | None = None,
                       cache: planlib.PlanCache | None = None,
                       impl: str | None = None,
                       search_impl: str | None = None, bm: int = 128,
                       bo: int | None = None):
    """Subm3 + inference BatchNorm + ReLU with the fused epilogue (§14).

    The BN affine (conv bias folded in) and the ReLU run on the output
    block while it is still VMEM-resident, and the kernel emits the next
    layer's activation-sparsity masks in passing. Returns
    ``(SparseTensor, ActSparsity)``; thread the act into the next Subm3's
    ``act=`` to skip its liveness re-sweep. Inference-only — training
    composes subm_conv3 + batch_norm + relu unfused.
    """
    from repro.kernels.spconv_gemm import ops as sg_ops
    if plan is None:
        plan = planlib.subm3_plan(st.coords, st.batch, st.valid,
                                  max_blocks=max_blocks, method=method,
                                  grid_bits=grid_bits, batch_bits=batch_bits,
                                  bm=bm, bo=bo, search_impl=search_impl,
                                  cache=cache)
    scale, shift = fold_bn_inference(conv_params.get("b"), bn_params,
                                     eps=eps)
    epi = sg_ops.FusedEpilogue(scale=scale, shift=shift, valid=st.valid)
    out, out_act = planlib.execute(plan, st.feats, conv_params["w"], None,
                                   spac=spac, act=act, epilogue=epi,
                                   impl=impl, src_valid=st.valid)
    return st.replace_feats(out), out_act


def gconv2(st: SparseTensor, params: dict, *, grid_bits: int = 7,
           batch_bits: int = 4, plan: planlib.ConvPlan | None = None,
           cache: planlib.PlanCache | None = None, impl: str | None = None,
           bm: int = 128,
           bo: int | None = None) -> tuple[SparseTensor,
                                           mapsearch.StridedMaps]:
    """Generalized 2x2x2 stride-2 SpConv (downsampling). Output-stationary:
    each octree parent gathers its children through octant taps (§IV-D1).

    Returns the new tensor *and* the maps so Tconv2 can reuse them (§IV-D2).
    """
    if plan is None:
        plan = planlib.gconv2_plan(st.coords, st.batch, st.valid,
                                   grid_bits=grid_bits,
                                   batch_bits=batch_bits, bm=bm, bo=bo,
                                   cache=cache)
    out = planlib.execute(plan, st.feats, params["w"], params["b"],
                          spac=False, impl=impl)
    out = jnp.where(plan.out_valid[:, None], out, 0)
    new = SparseTensor(coords=plan.out_coords, batch=plan.out_batch,
                       valid=plan.out_valid, feats=out)
    return new, plan.maps


def gconv3(st: SparseTensor, params: dict, *, grid_bits: int = 7,
           batch_bits: int = 4, dataflow: str = "output_stationary",
           plan: planlib.ConvPlan | None = None,
           cache: planlib.PlanCache | None = None, impl: str | None = None,
           bm: int = 128,
           bo: int | None = None) -> tuple[SparseTensor,
                                           mapsearch.StridedMaps]:
    """Generalized 3x3x3 stride-2 SpConv. The paper runs this input-
    stationary (§IV-D3); both dataflows are provided and agree bit-for-bit
    (tests) — the output-stationary one is the TPU perf path (pure gathers,
    gather-fused kernel).

    A stride-2 window can touch more downsampled output sites than there
    are inputs, so the default ``out_budget = st.n_max`` may overflow —
    the build replans at escalated budget (runtime/guard.with_replan,
    DESIGN.md §11; pre-PR-6 the overflowing sites were silently
    truncated). The escalated budget is memoized per shape class, so a
    loop pays the probe once. With ``REPRO_GUARD_REPLAN=0`` the
    overflow raises instead.
    """
    if plan is None:
        from repro.runtime import guard

        def build(budget):
            return planlib.gconv3_plan(
                st.coords, st.batch, st.valid, grid_bits=grid_bits,
                batch_bits=batch_bits, out_budget=budget, bm=bm, bo=bo,
                with_tiles=dataflow != "input_stationary", cache=cache)

        if guard.replan_retries() > 0:
            plan = guard.with_replan(
                build, st.n_max,
                key=("gconv3", st.n_max, grid_bits, batch_bits, dataflow))
        else:
            plan = build(st.n_max)
    m = plan.n_out
    if dataflow == "input_stationary":
        out = rulebook.apply_maps_scatter(st.feats, params["w"], plan.maps,
                                          params["b"], n_out=m, n_taps=27)
    else:
        out = planlib.execute(plan, st.feats, params["w"], params["b"],
                              spac=False, impl=impl)
        out = jnp.where(plan.out_valid[:, None], out, 0)
    new = SparseTensor(coords=plan.out_coords, batch=plan.out_batch,
                       valid=plan.out_valid, feats=out)
    return new, plan.maps


def tconv2(st: SparseTensor, params: dict, gconv2_maps: mapsearch.StridedMaps,
           target: SparseTensor, *, plan: planlib.ConvPlan | None = None,
           cache: planlib.PlanCache | None = None, impl: str | None = None,
           bm: int = 128, bo: int | None = None) -> SparseTensor:
    """Transposed 2x2x2 stride-2 SpConv: recovers the coordinate set from
    before the paired Gconv2 by transposing its maps (§IV-D2)."""
    if plan is None:
        plan = planlib.tconv2_plan(gconv2_maps, target.coords, target.batch,
                                   target.valid, bm=bm, bo=bo, cache=cache)
    out = planlib.execute(plan, st.feats, params["w"], params["b"],
                          spac=False, impl=impl)
    out = jnp.where(target.valid[:, None], out, 0)
    return SparseTensor(coords=target.coords, batch=target.batch,
                        valid=target.valid, feats=out)


# ---------------------------------------------------------------------------
# Norm / activation (Postprocessing Unit of Fig. 7)
# ---------------------------------------------------------------------------

def batch_norm(st: SparseTensor, params: dict, *, training: bool,
               momentum: float = 0.9, eps: float = 1e-5):
    """Masked BatchNorm over valid rows. Returns (tensor, updated_params)."""
    f = st.feats.astype(jnp.float32)
    mask = st.valid[:, None]
    if training:
        n = jnp.maximum(st.valid.sum(), 1).astype(jnp.float32)
        mean = (f * mask).sum(0) / n
        var = ((f - mean) ** 2 * mask).sum(0) / n
        new_params = {**params,
                      "mean": momentum * params["mean"] + (1 - momentum) * mean,
                      "var": momentum * params["var"] + (1 - momentum) * var}
    else:
        mean, var = params["mean"], params["var"]
        new_params = params
    y = (f - mean) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    y = jnp.where(mask, y, 0).astype(st.feats.dtype)
    return st.replace_feats(y), new_params


def relu(st: SparseTensor) -> SparseTensor:
    """The source of the paper's 40-60% inherent sparsity (Fig. 3(b))."""
    return st.replace_feats(jax.nn.relu(st.feats))
