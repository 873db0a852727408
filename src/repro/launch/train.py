"""End-to-end training driver (example application (b) + fault tolerance).

``make_train_step`` builds the jitted (state, batch) -> (state, metrics)
update used both by the CLI below (CPU-scale runs) and the dry-run lowering
(production mesh). The CLI trains a reduced-config model on the synthetic
token pipeline with checkpoint/restart via runtime.fault.TrainRunner:

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

``--arch minkunet`` instead runs the SpConv training loop
(:func:`run_spconv_demo`), the end-to-end face of the cross-step plan
cache (DESIGN.md §10): plans are built *eagerly* per step through one
long-lived content-addressed PlanCache, execution is jitted over the plan
constants, and a dataloader replaying the same cloud — every array
freshly allocated — performs map search once per stage geometry
(2*len(enc)+1 searches for the whole run, flat in the step count).
``benchmarks/cache_model.py`` and tests/test_cache_content.py gate on
exactly this loop.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data.tokens import FrameStream, TokenStream
from repro.models import api
from repro.optim import adamw
from repro.runtime import guard
from repro.runtime.fault import RunnerConfig, TrainRunner


def make_train_step(model: api.Model, opt_cfg: adamw.AdamWConfig):
    def train_step(state, batch):
        params, opt_state = state
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        params, opt_state, om = adamw.update(opt_cfg, grads, opt_state, params)
        return (params, opt_state), {**metrics, "loss": loss, **om}

    return train_step


def init_state(model: api.Model, seed: int = 0):
    params = model.init(jax.random.key(seed))
    return params, adamw.init(params)


def make_stream(cfg, batch: int, seq: int, seed: int = 0):
    if cfg.family == "encoder":
        return FrameStream(dim=cfg.frontend_dim, vocab=cfg.vocab,
                           batch=batch, seq=seq, seed=seed)
    if cfg.family == "vlm":
        base = TokenStream(vocab=cfg.vocab, batch=batch, seq=seq, seed=seed)
        p, v = cfg.n_patches, cfg.vision_dim

        class VLMStream:
            def batch_at(self, step):
                rng = np.random.default_rng(
                    np.random.SeedSequence([seed, step, 2]))
                b = base.batch_at(step)
                b["patches"] = rng.standard_normal((batch, p, v)).astype(
                    np.float32)
                return b

        return VLMStream()
    return TokenStream(vocab=cfg.vocab, batch=batch, seq=seq, seed=seed)


# ---------------------------------------------------------------------------
# SpConv training loop: cross-step plan reuse (DESIGN.md §10)
# ---------------------------------------------------------------------------

def make_spconv_step(cfg, opt_cfg, plans, *, impl: str | None = None):
    """Jitted (state, batch) -> (state, metrics) over *constant* plans.

    The plans were built eagerly (models.minkunet.build_plans), so the
    trace contains no map search — geometry enters as baked-in constants
    and only the stream tier (features, labels, params) flows through as
    arguments. ``donate_argnums=0`` donates the optimizer state, the
    buffer-reuse pattern the content-addressed cache exists for.
    """
    from repro.models import minkunet

    def step(state, batch):
        params, opt_state = state
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: minkunet.segmentation_loss(p, batch, cfg, plans=plans,
                                                 impl=impl),
            has_aux=True)(params)
        params, opt_state, om = adamw.update(opt_cfg, grads, opt_state,
                                             params)
        return (params, opt_state), {**metrics, "loss": loss, **om}

    return jax.jit(step, donate_argnums=0)


def run_spconv_demo(steps: int = 2, *, voxels: int = 128, cfg=None,
                    impl: str | None = None, seed: int = 0, cache=None,
                    scene: str = "indoor", replay: bool = True,
                    faults=None, ckpt_dir: str | None = None,
                    max_blocks: int | None = None, validate=None,
                    verify_cache: bool = False,
                    max_retries_per_step: int = 2,
                    persist_dir: str | None = None, resume: bool = False,
                    total_steps: int | None = None) -> dict:
    """Train MinkUNet for ``steps`` steps with cross-step plan caching.

    Every step re-voxelizes the scene into **freshly allocated** arrays
    (with ``replay=True`` the same scene every step — the dataloader-
    replay / donated-buffer pattern). Identity keys alone would miss on
    every step; the content-addressed PlanCache hits, so map search runs
    exactly ``len(enc) + (len(enc) + 1)`` times total, independent of
    ``steps``, and the compiled step function is reused because the
    cached plan objects are identical (`MinkPlans` identity keys the
    jitted-fn memo).

    ``impl`` None resolves the backend per host (``REPRO_KERNEL_IMPL`` /
    the fused Pallas kernel on TPU, the pure-jnp ``'ref'`` elsewhere —
    the CLI's ``--impl auto``); callers that need the CPU oracle on any
    host pass ``impl='ref'``.

    This loop is also the end-to-end face of the hardened runtime
    (DESIGN.md §11): every cloud passes through the ingress sanitizer
    (``validate``: a CloudPolicy, or None for the REPRO_GUARD_VALIDATE
    default), plan builds are overflow-adaptive (``max_blocks`` below
    the scene's block count triggers escalated replans instead of a
    raise), and the whole loop runs under a checkpoint/restart
    :class:`~repro.runtime.fault.TrainRunner` with a zero skip budget —
    so an injected :class:`~repro.runtime.fault.FaultPlan` (``faults``)
    must be survived by retry/fallback/replay alone, leaving the final
    state **bit-identical** to the fault-free run. ``state_digest`` in
    the result is what benchmarks/chaos.py compares.

    Returns a result dict consumed by the CI gates
    (benchmarks/cache_model.py, benchmarks/chaos.py,
    tests/test_cache_content.py, tests/test_robustness.py): ``losses``,
    ``mapsearch_calls``, ``searches_per_cloud`` (the expected flat
    count), ``compiled_steps``, the cache's :meth:`stats`, plus
    ``state_digest``, ``recoveries`` / ``skipped_batches`` /
    ``ckpt_failures`` and the run's health-counter ``health`` delta.

    Warm restarts (DESIGN.md §13): with ``persist_dir`` the PlanCache
    and PinnedStore are backed by a durable
    :class:`~repro.runtime.persist.SnapshotStore` under
    ``<persist_dir>/snap`` — a restarted demo replays previously-seen
    geometries with **zero** map searches (``mapsearch_calls == 0`` on a
    warm dir) — and ``resume=True`` continues from the newest *verified*
    checkpoint in ``ckpt_dir``. ``total_steps`` pins the lr-schedule
    horizon independently of ``steps``, so a killed-and-resumed run
    reaches a state **bit-identical** to the uninterrupted one
    (benchmarks/restart_replay.py gates on exactly this).
    """
    import hashlib
    import os as _os
    import tempfile

    from repro.core import plan as planlib, spconv
    from repro.data import pointcloud
    from repro.models import minkunet
    from repro.runtime import fault as faultlib, feature_cache, guard

    cfg = cfg or minkunet.MinkUNetConfig(stem=8, enc=(8, 16), dec=(16, 8),
                                         classes=4, blocks=1)
    params = minkunet.init_model(cfg, jax.random.key(seed))
    opt_cfg = adamw.AdamWConfig(lr=1e-3,
                                total_steps=max(total_steps or steps, 2),
                                warmup_steps=1)
    state = (params, adamw.init(params))
    pstore = None
    if persist_dir:
        from repro.runtime import persist as persistlib
        pstore = persistlib.SnapshotStore(_os.path.join(persist_dir, "snap"))
    if cache is None:
        cache = planlib.PlanCache(
            verify=verify_cache, persist=pstore,
            pinned=feature_cache.PinnedStore(persist=pstore)
            if pstore is not None else None)
    planlib.reset_mapsearch_counter()
    h0 = guard.health().snapshot()

    def cloud_at(step: int) -> dict:
        rng = np.random.default_rng(seed if replay else seed + step)
        vb = pointcloud.make_batch(rng, scene, batch_size=1,
                                   max_voxels=voxels)
        b = {k: jax.numpy.asarray(np.array(v))      # always fresh buffers
             for k, v in vb._asdict().items()}
        b["labels"] = jax.numpy.clip(b["labels"], 0, cfg.classes - 1)
        # ingress guard: sanitize the cloud before it reaches the plan
        # layer (a clean cloud passes the original buffers through)
        st, _ = spconv.make_sparse_tensor(
            b["coords"], b["batch"], b["valid"], b["feats"],
            grid_bits=cfg.grid_bits, batch_bits=cfg.batch_bits,
            policy=validate)
        b.update(coords=st.coords, batch=st.batch, valid=st.valid,
                 feats=st.feats)
        return b

    from collections import OrderedDict
    # compiled-step memo keyed by plan-object identity: a content hit
    # returns the same plan objects, so the replay loop reuses one
    # executable. Bounded FIFO — a non-replaying stream would otherwise
    # pin one MinkPlans + XLA executable per step forever.
    step_fns: OrderedDict = OrderedDict()
    compiled = [0]

    def runner_step(state, batch):
        faultlib.check(faultlib.KILL_SITE)     # mid-step SIGKILL point
        plans = minkunet.build_plans(batch["coords"], batch["batch"],
                                     batch["valid"], cfg, cache=cache,
                                     n_max=max_blocks)
        key = tuple(id(p) for part in plans for p in part)
        fn = step_fns.get(key)
        if fn is None:
            fn = make_spconv_step(cfg, opt_cfg, plans, impl=impl)
            while len(step_fns) >= 8:
                step_fns.popitem(last=False)
            step_fns[key] = fn
            compiled[0] += 1
        return fn(state, batch)

    # zero skip budget: a skipped batch changes the final state by
    # construction, and the chaos gate demands bit-identical recovery
    runner = TrainRunner(
        RunnerConfig(
            ckpt_dir=ckpt_dir or tempfile.mkdtemp(prefix="spconv-ckpt-"),
            ckpt_every=1, keep=2,
            max_retries_per_step=max_retries_per_step,
            max_skipped_batches=0),
        runner_step, cloud_at, state)
    resumed_from = None
    if resume and runner.restore_latest():
        resumed_from = runner.step
    with faultlib.inject(faults):
        losses = runner.run(steps)

    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(runner.state):
        digest.update(np.asarray(leaf).tobytes())
    return {
        "steps": steps,
        "losses": losses,
        "mapsearch_calls": planlib.mapsearch_call_count(),
        "searches_per_cloud": 2 * len(cfg.enc) + 1,
        "compiled_steps": compiled[0],
        "cache": cache.stats(),
        "state_digest": digest.hexdigest(),
        "recoveries": runner.recoveries,
        "skipped_batches": runner.skipped_batches,
        "ckpt_failures": runner.ckpt_failures,
        "resumed_from": resumed_from,
        "persist": pstore.stats() if pstore is not None else None,
        "health": guard.health().delta(h0),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro-ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture (default: reduced)")
    ap.add_argument("--voxels", type=int, default=512,
                    help="cloud budget for --arch minkunet")
    ap.add_argument("--impl", default="auto",
                    help="rulebook-execution backend for --arch minkunet: "
                         "auto (REPRO_KERNEL_IMPL / fused kernel on TPU) | "
                         "pallas | interpret | ref | xla")
    ap.add_argument("--health-json", default=None,
                    help="write the RuntimeHealth snapshot as structured "
                         "JSON to this path after the run")
    ap.add_argument("--persist-dir", default=None,
                    help="durable snapshot-store directory for warm "
                         "restarts (default: REPRO_PERSIST_DIR; unset "
                         "disables persistence) — DESIGN.md §13")
    ap.add_argument("--resume", action="store_true",
                    help="resume --arch minkunet from the newest verified "
                         "checkpoint in --ckpt-dir")
    ap.add_argument("--total-steps", type=int, default=None,
                    help="lr-schedule horizon when resuming a partial run "
                         "(default: --steps)")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    if args.arch == "minkunet":
        from repro.runtime import persist as persistlib
        res = run_spconv_demo(steps=args.steps, voxels=args.voxels,
                              impl=None if args.impl == "auto" else args.impl,
                              persist_dir=args.persist_dir
                              or persistlib.default_dir(),
                              ckpt_dir=args.ckpt_dir if args.resume else None,
                              resume=args.resume,
                              total_steps=args.total_steps)
        # a warm restart rehydrates every plan from the persist dir, so
        # zero searches is the best case, not a broken flat count
        warm = res["persist"] is not None and res["mapsearch_calls"] == 0
        flat = res["mapsearch_calls"] == res["searches_per_cloud"]
        print(f"arch=minkunet steps={res['steps']} "
              f"first_loss={res['losses'][0]:.4f} "
              f"last_loss={res['losses'][-1]:.4f} "
              f"map_searches={res['mapsearch_calls']} "
              f"(flat={'warm' if warm else 'yes' if flat else 'NO'}) "
              f"compiled_steps={res['compiled_steps']} "
              f"content_hits={res['cache']['content_hits']} "
              f"recoveries={res['recoveries']} "
              f"digest={res['state_digest'][:12]}")
        if args.health_json:
            guard.dump_health_json(args.health_json,
                                   meta={"arch": "minkunet",
                                         "steps": res["steps"],
                                         "digest": res["state_digest"]})
        return

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.reduced()
    model = api.build_model(cfg)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(args.steps // 20, 5))
    step_fn = jax.jit(make_train_step(model, opt_cfg))
    state = init_state(model)
    stream = make_stream(cfg, args.batch, args.seq)

    runner = TrainRunner(
        RunnerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
        lambda st, b: step_fn(st, jax.tree.map(jax.numpy.asarray, b)),
        stream.batch_at, state)
    if runner.restore_latest():
        print(f"resumed from step {runner.step}")
    t0 = time.time()
    losses = runner.run(args.steps)
    dt = time.time() - t0
    print(f"arch={cfg.name} steps={len(losses)} "
          f"first_loss={losses[0]:.4f} last_loss={losses[-1]:.4f} "
          f"({dt / max(len(losses), 1):.3f}s/step)")
    if args.health_json:
        guard.dump_health_json(args.health_json,
                               meta={"arch": cfg.name, "steps": len(losses)})


if __name__ == "__main__":
    main()
