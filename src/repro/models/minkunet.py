"""MinkowskiUNet [5] — the paper's segmentation benchmark (Seg(i)/Seg(o)).

Sparse UNet over the SpOctA core: Subm3 feature blocks, Gconv2 downsampling,
Tconv2 upsampling with exact coordinate recovery (§IV-D2) + skip concat.
``small`` ~ Seg(i) (ScanNet-sized), ``large`` ~ Seg(o) (SemanticKITTI-sized).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import plan as planlib
from repro.core import spconv
from repro.core.spconv import SparseTensor


@dataclass(frozen=True)
class MinkUNetConfig:
    name: str = "minkunet-small"
    in_ch: int = 4
    classes: int = 20
    stem: int = 32
    enc: tuple = (32, 64, 128, 256)
    dec: tuple = (128, 96, 96, 96)
    blocks: int = 1                 # Subm3 convs per stage
    grid_bits: int = 7
    batch_bits: int = 4
    map_method: str = "octree"      # paper | 'sorted' beyond-paper variant
    spac: bool = True               # §V-B sparsity-aware elision
    bm: int = 128                   # rulebook tile rows (kernel m-tile)
    bo: int | None = None           # output-stationary block rows (None:
                                    # build default, DESIGN.md §5)
    fused_epilogue: bool = False    # fuse BN+ReLU into the Subm3 kernel and
                                    # thread activation sparsity between
                                    # stacked blocks (inference only, §14)


SMALL = MinkUNetConfig()
LARGE = MinkUNetConfig(name="minkunet-large", stem=32,
                       enc=(64, 128, 256, 512), dec=(256, 192, 128, 128),
                       blocks=2)


def _conv_bn(key, k_taps, cin, cout):
    return {"conv": spconv.init_conv(key, k_taps, cin, cout),
            "bn": spconv.init_batchnorm(cout)}


def init_model(cfg: MinkUNetConfig, key) -> dict:
    ks = iter(jax.random.split(key, 64))
    p = {"stem": _conv_bn(next(ks), 27, cfg.in_ch, cfg.stem)}
    c_prev = cfg.stem
    skips = [cfg.stem]
    for i, c in enumerate(cfg.enc):
        stage = {"down": _conv_bn(next(ks), 8, c_prev, c)}
        for b in range(cfg.blocks):
            stage[f"block{b}"] = _conv_bn(next(ks), 27, c, c)
        p[f"enc{i}"] = stage
        c_prev = c
        skips.append(c)
    for i, c in enumerate(cfg.dec):
        skip_c = skips[-(i + 2)]
        stage = {"up": _conv_bn(next(ks), 8, c_prev, c)}
        for b in range(cfg.blocks):
            cin = c + skip_c if b == 0 else c
            stage[f"block{b}"] = _conv_bn(next(ks), 27, cin, c)
        p[f"dec{i}"] = stage
        c_prev = c
    p["head"] = spconv.init_conv(next(ks), 1, c_prev, cfg.classes)
    return p


def _apply_subm(st, params, cfg, training, n_max, cache, impl, plan=None,
                act=None):
    """One Subm3 + BN + ReLU block. Returns ``(st, act)`` where act is the
    fused epilogue's emitted ActSparsity (None on the unfused path) — feed
    it to the next block at the same resolution so its SPAC liveness
    refresh costs no HBM sweep (DESIGN.md §14)."""
    if cfg.fused_epilogue and not training:
        with jax.named_scope("fwd.subm3"):
            return spconv.subm_conv3_bn_relu(
                st, params["conv"], params["bn"], max_blocks=n_max,
                method=cfg.map_method, grid_bits=cfg.grid_bits,
                batch_bits=cfg.batch_bits, spac=cfg.spac, act=act,
                plan=plan, cache=cache, impl=impl, bm=cfg.bm, bo=cfg.bo)
    with jax.named_scope("fwd.subm3"):
        st = spconv.subm_conv3(st, params["conv"], max_blocks=n_max,
                               method=cfg.map_method,
                               grid_bits=cfg.grid_bits,
                               batch_bits=cfg.batch_bits, spac=cfg.spac,
                               act=act, plan=plan, cache=cache, impl=impl,
                               bm=cfg.bm, bo=cfg.bo)
    return _bn_relu(st, params["bn"], training), None


def _bn_relu(st, bn, training):
    with jax.named_scope("fwd.bn_relu"):
        st, _ = spconv.batch_norm(st, bn, training=training)
        return spconv.relu(st)


class MinkPlans(NamedTuple):
    """Every geometry-determined plan of one MinkUNet pass.

    Built eagerly by :func:`build_plans` (content-addressed, so a training
    loop replaying the same cloud gets the *same* plan objects back every
    step) and consumed by :func:`forward` via ``plans=`` — the plans then
    enter the jitted step as constants, and plan-object identity is a
    ready-made compiled-step cache key (launch/train.py does exactly
    this).
    """

    subm: tuple   # per resolution r = 0..len(enc): the Subm3 stage plan
    down: tuple   # per encoder stage: the Gconv2 plan (carries .maps)
    up: tuple     # per decoder stage: the Tconv2 plan


@functools.partial(jax.profiler.annotate_function, name="plan.build")
def build_plans(coords, batch, valid, cfg: MinkUNetConfig, *,
                cache: planlib.PlanCache | None = None,
                n_max: int | None = None,
                replan: bool | None = None) -> MinkPlans:
    """Build (or fetch) the full plan set for one coordinate set.

    Pure geometry — no features, no parameters — so it can run eagerly
    outside the training step while execution stays jitted. With a
    long-lived content-addressed ``cache``, a re-allocated identical
    cloud (dataloader replay, donated buffers) returns the cached plan
    objects and performs **zero** map searches; a fresh cloud pays
    ``len(enc)`` Gconv2 searches + ``len(enc) + 1`` Subm3 searches
    (Tconv2 reuses the Gconv2 maps and never searches, §IV-D2).

    ``replan`` wraps every Subm3 build in
    :func:`repro.runtime.guard.with_replan`: a scene occupying more
    16^3 blocks than ``n_max`` rebuilds at geometrically escalated
    ``max_blocks`` instead of raising (DESIGN.md §11). None resolves
    from ``REPRO_GUARD_REPLAN`` (on unless 0). Escalated capacities are
    memoized per shape class, so a replaying training loop stays flat
    on map-search count from step 2 on.
    """
    assert len(cfg.dec) <= len(cfg.enc), "decoder deeper than encoder"
    from repro.runtime import guard
    if replan is None:
        replan = guard.replan_retries() > 0
    if cache is None:
        cache = planlib.PlanCache()
    n_max = coords.shape[0] if n_max is None else n_max
    gb, bb = cfg.grid_bits, cfg.batch_bits

    def subm(r, c, b, v):
        def build(mb):
            return planlib.subm3_plan(c, b, v, max_blocks=mb,
                                      method=cfg.map_method, grid_bits=gb,
                                      batch_bits=bb, bm=cfg.bm, bo=cfg.bo,
                                      cache=cache)
        with TraceAnnotation("plan.subm3", r=r):
            if not replan:
                return build(n_max)
            return guard.with_replan(build, n_max,
                                     key=("minkunet-subm3", c.shape[0], gb,
                                          bb))

    # ``r`` on each stage's span: the resolution its outputs live at
    cur = (coords, batch, valid)
    subms, downs, stack = [subm(0, *cur)], [], [cur]
    for i in range(len(cfg.enc)):
        with TraceAnnotation("plan.gconv2", r=i + 1):
            d = planlib.gconv2_plan(*cur, grid_bits=gb, batch_bits=bb,
                                    bm=cfg.bm, bo=cfg.bo, cache=cache)
        cur = (d.out_coords, d.out_batch, d.out_valid)
        downs.append(d)
        subms.append(subm(i + 1, *cur))
        stack.append(cur)
    ups = []
    for i in range(len(cfg.dec)):
        target = stack[-(i + 2)]
        with TraceAnnotation("plan.tconv2", r=len(cfg.enc) - 1 - i):
            ups.append(planlib.tconv2_plan(downs[-(i + 1)].maps, *target,
                                           bm=cfg.bm, bo=cfg.bo, cache=cache))
    return MinkPlans(tuple(subms), tuple(downs), tuple(ups))


def forward(params, st: SparseTensor, cfg: MinkUNetConfig, *,
            training: bool = False,
            cache: planlib.PlanCache | None = None,
            plans: MinkPlans | None = None,
            impl: str | None = None) -> jnp.ndarray:
    """Returns per-voxel class logits (N, classes).

    A per-forward PlanCache shares map search across every layer on the
    same coordinate set: B stacked Subm3 blocks search once, and decoder
    stages reuse the encoder-stage plans at the same resolution
    (coordinates are recovered exactly by Tconv2, §IV-D2). Pass a
    longer-lived ``cache`` to extend the reuse across calls — its content
    keys make *re-allocated* identical clouds hit too (DESIGN.md §10) —
    or prebuild the geometry with :func:`build_plans` and pass ``plans=``
    so the forward performs no plan lookups at all (the training-loop
    arrangement: eager plan build, jitted execution over plan constants).
    """
    if plans is None and cache is None:
        cache = planlib.PlanCache()
    n_max = st.n_max
    n_enc = len(cfg.enc)
    with jax.named_scope("fwd.subm3"):
        st = spconv.mask_feats(st)
    st, _ = _apply_subm(st, params["stem"], cfg, training, n_max, cache,
                        impl, plan=plans.subm[0] if plans else None)

    skips, maps_stack = [st], []
    gb = cfg.grid_bits
    for i in range(n_enc):
        stage = params[f"enc{i}"]
        with jax.named_scope("fwd.down"):
            down, maps = spconv.gconv2(
                st, stage["down"]["conv"], grid_bits=gb,
                batch_bits=cfg.batch_bits,
                plan=plans.down[i] if plans else None, cache=cache,
                impl=impl, bm=cfg.bm, bo=cfg.bo)
        st = _bn_relu(down, stage["down"]["bn"], training)
        act = None    # new resolution/channels: previous masks don't apply
        for b in range(cfg.blocks):
            st, act = _apply_subm(st, stage[f"block{b}"], cfg, training,
                                  n_max, cache, impl,
                                  plan=plans.subm[i + 1] if plans else None,
                                  act=act)
        maps_stack.append(maps)
        skips.append(st)

    for i in range(len(cfg.dec)):
        stage = params[f"dec{i}"]
        maps = maps_stack[-(i + 1)]
        target = skips[-(i + 2)]
        with jax.named_scope("fwd.up"):
            up = spconv.tconv2(st, stage["up"]["conv"], maps, target,
                               plan=plans.up[i] if plans else None,
                               cache=cache, impl=impl, bm=cfg.bm, bo=cfg.bo)
        up = _bn_relu(up, stage["up"]["bn"], training)
        with jax.named_scope("fwd.concat"):
            st = up.replace_feats(
                jnp.concatenate([up.feats, target.feats], axis=-1))
        act = None    # concat changed the channel layout: masks are stale
        for b in range(cfg.blocks):
            st, act = _apply_subm(st, stage[f"block{b}"], cfg, training,
                                  n_max, cache, impl,
                                  plan=plans.subm[n_enc - 1 - i]
                                  if plans else None, act=act)

    # f32 end to end: at default precision a TPU would round this dot's
    # operands to bf16 (the sparse convs run at fp32 in-kernel, §6)
    with jax.named_scope("fwd.head"):
        logits = jnp.dot(st.feats, params["head"]["w"][0],
                         precision=jax.lax.Precision.HIGHEST)
        logits = logits + params["head"]["b"]
        return jnp.where(st.valid[:, None], logits, 0)


def forward_multicloud(params, clouds, cfg: MinkUNetConfig, *,
                       training: bool = False,
                       cache: planlib.PlanCache | None = None,
                       impl: str | None = None,
                       plans=None, forward_fn=None, on_error=None) -> list:
    """Batched multi-cloud inference: per-voxel logits for each cloud.

    Serving-scale entry point: run it under an active device mesh and
    every map search routes through the sharded OCTENT engine
    (kernels/octent/sharded.py) while rulebook execution follows the
    mesh's tensor sharding. Each cloud keeps its own plans — plan keys
    are coordinate-array identities *and* content fingerprints plus the
    mesh fingerprint (DESIGN.md §10), so the shared cache naturally
    separates distinct clouds, still reuses plans *within* each cloud's
    enc/dec stages (one search per resolution), and deduplicates
    repeated clouds across requests: a client re-sending the same scene
    (or the same cloud appearing twice in one batch) hits by content
    even though every buffer is new. The cache is sized so no cloud
    evicts another's stage plans mid-pass.

    The serving engine (launch/spconv_serve.py, DESIGN.md §12) drives
    this with all three hooks:

      * ``plans`` — per-cloud prebuilt :class:`MinkPlans` (aligned with
        ``clouds``); plan build then happens eagerly at admission, and
        the forward performs no lookups.
      * ``forward_fn`` — ``(params, st, plans_i) -> logits`` override,
        the engine's per-bucket *compiled* executable (plans threaded as
        traced arguments, one trace per padding-bucket class).
      * ``on_error`` — ``(index, exc) -> result`` per-request fault
        isolation: an exception while executing cloud *i* is routed
        here (retry / quarantine / placeholder) instead of aborting the
        batchmates. None keeps the raising behavior.
    """
    if cache is None:
        per_cloud = 2 * (len(cfg.enc) + len(cfg.dec)) + 2
        cache = planlib.PlanCache(capacity=max(64, per_cloud * len(clouds)))
    out = []
    for i, st in enumerate(clouds):
        try:
            if forward_fn is not None:
                r = forward_fn(params, st,
                               plans[i] if plans is not None else None)
            else:
                r = forward(params, st, cfg, training=training, cache=cache,
                            impl=impl,
                            plans=plans[i] if plans is not None else None)
        except Exception as e:                       # noqa: BLE001
            if on_error is None:
                raise
            r = on_error(i, e)
        out.append(r)
    return out


def segmentation_loss(params, batch, cfg: MinkUNetConfig, *,
                      plans: MinkPlans | None = None,
                      impl: str | None = None):
    """batch: SparseTensor fields + labels (N,) int32. ``plans`` skips
    in-trace plan building (see :func:`build_plans`); ``impl`` selects
    the rulebook-execution backend as in :func:`forward`."""
    st = SparseTensor(batch["coords"], batch["batch"], batch["valid"],
                      batch["feats"])
    logits = forward(params, st, cfg, training=True, plans=plans, impl=impl)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, batch["labels"][:, None], -1)[:, 0]
    nll = jnp.where(st.valid, lse - ll, 0.0)
    loss = nll.sum() / jnp.maximum(st.valid.sum(), 1)
    acc = jnp.where(st.valid, jnp.argmax(logits, -1) == batch["labels"], False)
    acc = acc.sum() / jnp.maximum(st.valid.sum(), 1)
    return loss, {"ce": loss, "acc": acc}
