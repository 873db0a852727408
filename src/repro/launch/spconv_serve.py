"""Continuous-batching SpConv serving engine (DESIGN.md §12).

The "millions of users" integration layer over everything PRs 1-6
built: requests enter through the bounded, bucket-quantizing
:class:`~repro.runtime.admission.AdmissionQueue`, plans resolve through
one long-lived content-addressed PlanCache (repeated scenes search
zero extra times), and execution runs one request at a time through a
**per-bucket compiled executable**: plan arrays are threaded into the
jitted forward as *traced arguments* over a static skeleton, so every
request in a padding bucket replays one XLA executable — the engine
compiles once per bucket class, never once per request geometry.

The engine serves any model in ``MODELS``, picked by the type of its
config. A model module gives ``build_plans(coords, batch, valid, cfg,
*, cache, n_max)`` (called by attribute per request), ``SERVE_STAGES``
(its forward as one or more executables, each later one launched inside
its own span; MinkUNet's is one, SECOND's two) and
``plans_per_cloud(cfg)`` (the PlanCache's sizing).

Robustness posture:

  * **Per-request fault isolation** — each request's plan build and
    forward run under a retry-once guard: a transient fault (an
    injected one-shot, a flaky lowering) recovers with the same impl
    and a bit-identical result; a persistent one quarantines *that
    request only* with a typed
    :data:`~repro.runtime.admission.ISOLATED_FAULT` outcome.
    Batchmates' results stay bit-identical to a fault-free run —
    ``benchmarks/serve_replay.py`` gates on exactly this.
  * **Graceful-degradation ladder** driven by
    :class:`~repro.runtime.guard.RuntimeHealth` deltas per tick:
    level 1 halves the batch size, level 2 forces the bit-exact ``ref``
    backend (the same oracle :func:`repro.runtime.guard.dispatch` falls
    back to), level 3 sheds the queue with a typed rejection. Healthy
    ticks walk the ladder back down.
  * **Deadline-aware shedding** — dequeue consults a per-bucket EWMA of
    service time; hopeless requests are shed, late answers never
    computed.
  * The ``batch`` fault site attacks batch assembly itself (retried
    once; a persistent failure isolates only that tick's requests).

CLI (a small demo of the full path; on a TPU it runs both Pallas kernels):

    PYTHONPATH=src python -m repro.launch.spconv_serve \
        --requests 12 --buckets 96,192 --health-json /tmp/health.json
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import hashlib
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import plan as planlib, sparsity
from repro.core.spconv import SparseTensor
from repro.models import minkunet, second
from repro.runtime import admission, fault, guard

#: the served models, by the type of their config
MODELS = {minkunet.MinkUNetConfig: minkunet, second.SECONDConfig: second}

# ---------------------------------------------------------------------------
# Plan splitting: traced arrays vs static skeleton
# ---------------------------------------------------------------------------

_ARRAY_TYPES = (jax.Array, np.ndarray)


def split_plans(plans):
    """Partition a model's plan pytree (``MinkPlans``, ``SECONDPlans``) into
    traced-array leaves and a hashable static skeleton.

    Returns ``(dyn, treedef, static, skeleton)``: ``dyn`` is the leaf
    list with non-array leaves replaced by None (None flattens away, so
    it passes through jit as a pytree of arrays only); ``static`` the
    complement; ``skeleton`` a hashable key — treedef + static leaves +
    array shapes/dtypes — identical for every geometry in one padding
    bucket, which is what makes the compiled-executable count equal the
    bucket-class count.
    """
    leaves, treedef = jax.tree_util.tree_flatten(plans)
    dyn = [lf if isinstance(lf, _ARRAY_TYPES) else None for lf in leaves]
    static = tuple(None if isinstance(lf, _ARRAY_TYPES) else lf
                   for lf in leaves)
    shapes = tuple((tuple(lf.shape), str(lf.dtype)) for lf in leaves
                   if isinstance(lf, _ARRAY_TYPES))
    return dyn, treedef, static, (treedef, static, shapes)


def merge_plans(treedef, static, dyn):
    """Inverse of :func:`split_plans` (runs under trace: ``dyn`` holds
    tracers where arrays were). Leaves are never None in these pytrees,
    so None is a safe placeholder marker."""
    leaves = [s if d is None else d for d, s in zip(dyn, static)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _staged(first, later):
    """One request's forward as several executables: ``first`` over the
    request's arrays, returning ``(x, extra)``, then each ``(span, fn)``
    of ``later`` over the previous ``x``, launched inside its span.
    Returns ``(answer, extra)``. Nothing is read back in between."""
    def run(params, *args):
        x, extra = first(params, *args)
        for span, fn in later:
            with TraceAnnotation(span):
                x = fn(params, x)
        return x, extra
    return run


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeResult:
    """Terminal outcome of one request."""

    rid: str
    status: str                  # completed | shed | rejected | isolated
    reason: str | None = None    # admission.* reason constant for non-ok
    bucket: int | None = None
    latency_s: float | None = None   # submit -> result ready (completed)
    degraded: bool = False       # served while the ladder was engaged
    digest: str | None = None    # sha256 of the answer's bytes
    logits: object = None        # the model's answer, np.ndarray, for
                                 # completed requests (MinkUNet: per-voxel
                                 # logits; SECOND: the head maps)


#: ladder levels (DESIGN.md §12): 0 healthy, 1 shrink batch, 2 ref
#: fallback, 3 shed
LADDER_MAX = 3


class ServeEngine:
    """Continuous-batching engine over a served model + the admission
    queue.

    Args:
      params, model_cfg: the served model (init once, serve many); the
        config's type picks the model module (``MODELS``).
      impl: primary rulebook-execution backend (None: resolved per host
        by ``spconv_gemm.ops.kernel_impl`` — the fused Pallas kernel on
        TPU, ``'ref'`` elsewhere). Ladder level 2 forces ``'ref'``, a
        documented degradation that the health counters record.
      queue: an :class:`~repro.runtime.admission.AdmissionQueue` (None:
        construct one from the flags with the model's grid contract).
      max_batch: requests drained per tick (None:
        ``REPRO_SERVE_MAX_BATCH``).
      clock: injectable time source (tests).
      verify_cache: content-hit verification on the shared PlanCache
        (detects injected fingerprint collisions).
      recover_after: healthy ticks before the ladder steps down a level.
      persist_dir: durability root (DESIGN.md §13). Plans and pinned
        search structures snapshot under ``<persist_dir>/snap`` (warm
        restarts replay seen geometries with zero map searches), and
        every admitted request journals under ``<persist_dir>/journal``
        until its terminal result — :meth:`recover` re-queues the
        journaled in-flight work after a crash, shedding past-deadline
        entries with the typed ``restart`` reason.

    ``submit`` + ``drain`` is the batch-replay arrangement
    (benchmarks/serve_replay.py); a live loop would interleave them.
    Terminal outcomes accumulate in ``results`` and the ``serve.*`` /
    ``admit.*`` health counters — the two ledgers agree exactly, and
    the serve gate asserts it.
    """

    def __init__(self, params, model_cfg, *,
                 impl: str | None = None,
                 queue: admission.AdmissionQueue | None = None,
                 max_batch: int | None = None, clock=time.monotonic,
                 verify_cache: bool = False, recover_after: int = 2,
                 persist_dir: str | None = None):
        import os
        self.params = params
        self.model_cfg = model_cfg
        self.model = MODELS[type(model_cfg)]
        self.impl = impl
        self.clock = clock
        self.queue = queue if queue is not None else admission.AdmissionQueue(
            grid_bits=model_cfg.grid_bits, batch_bits=model_cfg.batch_bits,
            clock=clock)
        self.max_batch = int(os.environ.get("REPRO_SERVE_MAX_BATCH", "8")) \
            if max_batch is None else max_batch
        self.persist = None
        self.journal = None
        pinned = None
        if persist_dir:
            from repro.runtime import feature_cache, persist as persistlib
            self.persist = persistlib.SnapshotStore(
                os.path.join(persist_dir, "snap"))
            self.journal = persistlib.SnapshotStore(
                os.path.join(persist_dir, "journal"))
            pinned = feature_cache.PinnedStore(persist=self.persist)
        self.cache = planlib.PlanCache(
            capacity=max(64, 8 * self.model.plans_per_cloud(model_cfg)),
            verify=verify_cache, persist=self.persist, pinned=pinned)
        self.recover_after = recover_after
        self.level = 0
        self._healthy_ticks = 0
        self._exec: dict = {}            # skeleton -> jitted executable
        self._refreshes: dict = {}       # skeleton -> SPAC refreshes/cloud
        self._last_refresh = None        # set by _forward_fn
        self.compiled = 0
        self._ewma: dict[int, float] = {}    # bucket -> service seconds
        self.results: list[ServeResult] = []
        self.ticks = 0

    # -- admission ----------------------------------------------------------

    def submit(self, rid: str, coords, batch, valid, feats, *,
               deadline_s: float | None = None):
        """Admit one raw request; a typed rejection is terminal and
        recorded immediately. Admitted requests journal to disk
        (DESIGN.md §13) until their terminal result, so a crash between
        admit and answer is recoverable, not silent loss."""
        out = self.queue.submit(rid, coords, batch, valid, feats,
                                deadline_s=deadline_s)
        if isinstance(out, admission.Rejection):
            self._record_rejection(out)
        elif self.journal is not None:
            # monotonic deadlines don't survive a process, so the journal
            # carries the remaining budget as a wall-clock expiry
            self.journal.put(("req", out.rid), {
                "rid": out.rid, "coords": out.coords, "batch": out.batch,
                "valid": out.valid, "feats": out.feats,
                "bucket": out.bucket, "n_valid": out.n_valid,
                "wall_deadline": time.time()
                + (out.deadline - self.queue.clock())})
        return out

    def recover(self) -> dict:
        """Re-queue journaled in-flight requests after a restart.

        Every verified journal entry whose deadline still holds is
        restored to the admission queue (``serve.recovered``); expired
        or un-restorable entries get a terminal typed ``restart``
        rejection. Corrupt journal files are dropped by the store
        (``persist.dropped``) — a torn journal write costs that one
        request, never the engine. Returns ``{"recovered", "shed"}``.
        """
        if self.journal is None:
            return {"recovered": 0, "shed": 0}
        recovered = shed = 0
        for key, val in list(self.journal.items()):
            if not (isinstance(key, tuple) and len(key) == 2
                    and key[0] == "req"):
                continue
            remaining = float(val["wall_deadline"]) - time.time()
            now = self.clock()
            req = admission.Request(
                val["rid"], np.asarray(val["coords"]),
                np.asarray(val["batch"]), np.asarray(val["valid"]),
                np.asarray(val["feats"]), int(val["bucket"]),
                int(val["n_valid"]), now + remaining, now)
            out = self.queue.restore(req)
            if isinstance(out, admission.Rejection):
                self._record_rejection(out)
                self.journal.delete(key)
                shed += 1
            else:
                guard.health().note("serve.recovered")
                recovered += 1
        return {"recovered": recovered, "shed": shed}

    def _record_rejection(self, rej: admission.Rejection) -> None:
        if rej.reason == admission.ISOLATED_FAULT:
            status = "isolated"
            guard.health().note("serve.isolated")
        elif rej.shed:
            status = "shed"
            guard.health().note("serve.shed")
        else:
            status = "rejected"
            guard.health().note("serve.rejected")
        self.results.append(ServeResult(rej.rid, status, reason=rej.reason))

    # -- per-bucket compiled executables -------------------------------------

    def _impl_now(self) -> str:
        return "ref" if self.level >= 2 else self.impl

    def _executable(self, skeleton, treedef, static, impl: str):
        key = (skeleton, impl)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        cfg, model = self.model_cfg, self.model
        (_, first), *later = model.SERVE_STAGES
        refreshes = self._refreshes

        @jax.jit
        def run(params, coords, batch, valid, feats, dyn):
            plans = merge_plans(treedef, static, dyn)
            st = SparseTensor(coords, batch, valid, feats)
            with sparsity.refresh_log() as log:
                out = getattr(model, first)(params, st, cfg, plans=plans,
                                            impl=impl)
            refreshes[key] = len(log)         # static: noted while tracing
            return out, sum(log, jnp.zeros((), jnp.int32))

        if later:
            run = _staged(run, [
                (span, jax.jit(functools.partial(getattr(model, name),
                                                 cfg=cfg)))
                for span, name in later])
        self._exec[key] = run
        self.compiled += 1
        guard.health().note("serve.compile")
        return run

    def _forward_fn(self, params, st: SparseTensor, plans):
        """Launch one request's forward; returns the answer alone. The
        executable's count of full SPAC refreshes is left, unread, in
        ``self._last_refresh`` as ``(refreshes per cloud, full count)``
        for ``serve.fetch`` to read after the answer."""
        with TraceAnnotation("serve.dispatch"):
            dyn, treedef, static, skeleton = split_plans(plans)
            impl = self._impl_now()
            fn = self._executable(skeleton, treedef, static, impl)
            out, full = fn(params, st.coords, st.batch, st.valid, st.feats,
                           dyn)
            self._last_refresh = (self._refreshes[(skeleton, impl)], full)
            return out

    # -- the continuous-batching tick ----------------------------------------

    def _effective_batch(self) -> int:
        return max(1, self.max_batch // (2 if self.level >= 1 else 1))

    def _est_service(self, bucket: int) -> float:
        return self._ewma.get(bucket, 0.0)

    def _note_service(self, bucket: int, dt: float) -> None:
        prev = self._ewma.get(bucket)
        self._ewma[bucket] = dt if prev is None else 0.8 * prev + 0.2 * dt

    def step(self) -> list[ServeResult]:
        """One tick: assemble a batch, execute it with per-request
        isolation, update the degradation ladder. Returns this tick's
        terminal results (also appended to ``self.results``). Journal
        entries of requests reaching a terminal state this tick are
        deleted — a kill *during* the tick (the ``kill`` fault site
        below) leaves them journaled for :meth:`recover`."""
        fault.check(fault.KILL_SITE)        # mid-tick SIGKILL point
        results = self._step()
        if self.journal is not None:
            for r in results:
                self.journal.delete(("req", r.rid))
        return results

    def _step(self) -> list[ServeResult]:
        self.ticks += 1
        with TraceAnnotation("serve.tick", tick=self.ticks) as span:
            h0 = guard.health().snapshot()
            tick_results: list[ServeResult] = []

            if self.level >= LADDER_MAX:
                for rej in self.queue.shed_all():
                    self._record_rejection(rej)
                    tick_results.append(self.results[-1])
                self._ladder_update(h0, had_failures=False)
                return tick_results

            with TraceAnnotation("serve.admit"):
                reqs, shed = self.queue.take(
                    self._effective_batch(), est_service_s=self._est_service)
            span.set_metadata(batch=len(reqs))
            for rej in shed:
                self._record_rejection(rej)
                tick_results.append(self.results[-1])
            if not reqs:
                self._ladder_update(h0, had_failures=False)
                return tick_results

            # the 'batch' fault site attacks batch assembly itself; one-shot
            # faults recover on the retry, persistent ones isolate only this
            # tick's requests
            batch_dead = None
            for attempt in (0, 1):
                try:
                    fault.check("batch")
                    break
                except fault.InjectedFault as e:
                    if attempt:
                        batch_dead = e
                    else:
                        guard.health().note("serve.batch_retry")
            if batch_dead is not None:
                for req in reqs:
                    guard.health().note("serve.isolated")
                    res = ServeResult(req.rid, "isolated",
                                      reason=admission.ISOLATED_FAULT,
                                      bucket=req.bucket)
                    self.results.append(res)
                    tick_results.append(res)
                self._ladder_update(h0, had_failures=True)
                return tick_results

            tick_results.extend(self._execute_batch(reqs))
            failed = any(r.status == "isolated" for r in tick_results)
            self._ladder_update(h0, had_failures=failed)
            return tick_results

    def _execute_batch(self, reqs) -> list[ServeResult]:
        degraded = self.level > 0
        built: list = [None] * len(reqs)
        sts: list = [None] * len(reqs)
        results: list[ServeResult | None] = [None] * len(reqs)

        def build_one(req):
            with TraceAnnotation("serve.build", rid=req.rid):
                c = jnp.asarray(req.coords)
                b = jnp.asarray(req.batch)
                v = jnp.asarray(req.valid)
                f = jnp.asarray(req.feats)
                plans = self.model.build_plans(c, b, v, self.model_cfg,
                                               cache=self.cache,
                                               n_max=req.bucket)
                return SparseTensor(c, b, v, f), plans

        for i, req in enumerate(reqs):
            try:
                sts[i], built[i] = build_one(req)
            except Exception as e:                   # noqa: BLE001
                try:                                 # transient faults
                    sts[i], built[i] = build_one(req)  # recover on retry
                    guard.health().note("serve.build_retry")
                except Exception:                    # noqa: BLE001
                    results[i] = self._isolate(req, e)

        live = [i for i in range(len(reqs)) if results[i] is None]

        def on_error(j, exc):
            # j indexes the *live* sublist; retry once (one-shot faults
            # recover bit-identically with the same impl), then isolate
            i = live[j]
            try:
                out = self._forward_fn(self.params, sts[i], built[i])
                guard.health().note("serve.exec_retry")
                return out
            except Exception:                        # noqa: BLE001
                results[i] = self._isolate(reqs[i], exc)
                return None

        outs, refreshes = [], []
        for j, i in enumerate(live):
            self._last_refresh = None
            try:
                outs.append(self._forward_fn(self.params, sts[i], built[i]))
            except Exception as e:                   # noqa: BLE001
                outs.append(on_error(j, e))
            refreshes.append(self._last_refresh)

        for j, i in enumerate(live):
            if results[i] is not None:
                continue
            req = reqs[i]
            with TraceAnnotation("serve.fetch", rid=req.rid):
                logits = np.asarray(outs[j])
                if refreshes[j] is not None:
                    n, full = refreshes[j]
                    guard.health().note("spac.refresh", n)
                    guard.health().note("spac.refresh_full", int(full))
            with TraceAnnotation("serve.finish", rid=req.rid):
                done = self.clock()
                self._note_service(req.bucket, done - req.submitted_at)
                guard.health().note("serve.completed")
                if degraded:
                    guard.health().note("serve.degraded")
                results[i] = ServeResult(
                    req.rid, "completed", bucket=req.bucket,
                    latency_s=done - req.submitted_at, degraded=degraded,
                    digest=hashlib.sha256(logits.tobytes()).hexdigest(),
                    logits=logits)
        final = [r for r in results if r is not None]
        self.results.extend(final)
        return final

    def _isolate(self, req, exc) -> ServeResult:
        guard.health().note("serve.isolated")
        return ServeResult(req.rid, "isolated",
                           reason=admission.ISOLATED_FAULT,
                           bucket=req.bucket)

    def _ladder_update(self, h0: dict, *, had_failures: bool) -> None:
        """Walk the degradation ladder from this tick's health delta."""
        delta = guard.health().delta(h0)
        bad = had_failures or any(
            k.startswith(("fallback.error", "quarantine.enter",
                          "replan.overflow")) for k in delta)
        if bad:
            self._healthy_ticks = 0
            if self.level < LADDER_MAX:
                self.level += 1
                guard.health().note("serve.degrade.enter")
                guard.health().note(f"serve.degrade.level{self.level}")
        else:
            self._healthy_ticks += 1
            if self.level > 0 and self._healthy_ticks >= self.recover_after:
                self.level -= 1
                self._healthy_ticks = 0
                guard.health().note("serve.degrade.exit")

    # -- driving -------------------------------------------------------------

    def drain(self, max_ticks: int = 10_000) -> list[ServeResult]:
        """Tick until the queue is empty; returns all terminal results."""
        while len(self.queue) and max_ticks > 0:
            self.step()
            max_ticks -= 1
        return self.results

    def stats(self) -> dict:
        by = {"completed": 0, "shed": 0, "rejected": 0, "isolated": 0}
        degraded = 0
        for r in self.results:
            by[r.status] += 1
            degraded += int(r.status == "completed" and r.degraded)
        lat = sorted(r.latency_s for r in self.results
                     if r.status == "completed")
        return {
            "requests": len(self.results), **by, "degraded": degraded,
            "ticks": self.ticks, "compiled": self.compiled,
            "level": self.level,
            "latency_p50_s": float(np.percentile(lat, 50)) if lat else None,
            "latency_p99_s": float(np.percentile(lat, 99)) if lat else None,
            "cache": self.cache.stats(),
            "persist": self.persist.stats() if self.persist else None,
            "journal": self.journal.stats() if self.journal else None,
        }


# ---------------------------------------------------------------------------
# CLI demo
# ---------------------------------------------------------------------------

def _demo_requests(n: int, buckets, seed: int = 0):
    from repro.data import pointcloud
    reqs = []
    for i in range(n):
        rng = np.random.default_rng(seed + i % max(1, n // 2))
        vox = int(buckets[i % len(buckets)] * 0.75)
        vb = pointcloud.make_batch(rng, "indoor" if i % 2 else "lidar",
                                   batch_size=1, max_voxels=vox)
        reqs.append((f"req-{i}", vb.coords, vb.batch, vb.valid, vb.feats))
    return reqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--buckets", default="",
                    help="comma-separated padding-bucket sizes "
                         "(default: REPRO_SERVE_BUCKETS)")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--impl", default="auto",
                    help="rulebook-execution backend: auto (REPRO_KERNEL_IMPL "
                         "/ the fused kernel on TPU, 'ref' elsewhere) | "
                         "pallas | interpret | ref")
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--health-json", default=None,
                    help="write the RuntimeHealth snapshot + serve stats "
                         "as JSON to this path")
    ap.add_argument("--persist-dir", default=None,
                    help="durability root for warm restarts + the request "
                         "journal (default: REPRO_PERSIST_DIR; unset "
                         "disables persistence) — DESIGN.md §13")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    buckets = tuple(int(x) for x in args.buckets.split(",") if x.strip()) \
        or admission.bucket_classes()
    cfg = minkunet.MinkUNetConfig(stem=8, enc=(8, 16), dec=(16, 8),
                                  classes=4, blocks=1)
    params = minkunet.init_model(cfg, jax.random.key(0))
    queue = admission.AdmissionQueue(buckets=buckets,
                                     grid_bits=cfg.grid_bits,
                                     batch_bits=cfg.batch_bits)
    from repro.runtime import persist as persistlib
    engine = ServeEngine(params, cfg,
                         impl=None if args.impl == "auto" else args.impl,
                         queue=queue,
                         max_batch=args.max_batch,
                         persist_dir=args.persist_dir
                         or persistlib.default_dir())
    rec = engine.recover()
    if rec["recovered"] or rec["shed"]:
        print(f"journal recovery: re-queued {rec['recovered']}, "
              f"shed {rec['shed']} past-deadline")
    t0 = time.monotonic()
    for rid, c, b, v, f in _demo_requests(args.requests, buckets):
        engine.submit(rid, c, b, v, f, deadline_s=args.deadline_s)
    engine.drain()
    wall = time.monotonic() - t0
    s = engine.stats()
    qps = s["completed"] / wall if wall > 0 else float("nan")
    print(f"served {s['completed']}/{s['requests']} "
          f"(shed={s['shed']} rejected={s['rejected']} "
          f"isolated={s['isolated']} degraded={s['degraded']}) "
          f"compiled={s['compiled']} executables over "
          f"{len(buckets)} buckets; "
          f"p50={1e3 * (s['latency_p50_s'] or 0):.0f}ms "
          f"p99={1e3 * (s['latency_p99_s'] or 0):.0f}ms "
          f"qps={qps:.2f}")
    if args.health_json:
        guard.dump_health_json(args.health_json,
                               meta={"engine": "spconv_serve", **{
                                   k: v for k, v in s.items()
                                   if not isinstance(v, dict)}})
        print(f"health snapshot -> {args.health_json}")


if __name__ == "__main__":
    main()
