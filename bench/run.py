#!/usr/bin/env python3
"""Benchmark harness: sparse 3D networks served to closed-loop clients on
the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json`` at the
root of the checkout: the configuration's file (``configs/<name>.json``),
its model family (``families/<family>.py``, named by the file's
``family``), the traffic mix (``traffic/<name>.json``) and, in a traced
run, one reader per per-layer metric (``metrics/<name>.py``, a
``read(ctx)`` function). Adding a cell, a configuration, a traffic mix, a
metric or a model family adds files and entries and edits none: past
``cell_spec`` the harness knows a model only through its family's hooks
(``FAMILY_HOOKS``).

A run has three phases:

1. Set-up (``setup_s``, from process start): weights from ``--seed`` on
   the device in one call, the traffic's base scenes, the family's engine
   with the traffic's single padding bucket, and one warm-up tick that
   compiles the bucket's executable and the eager plan-build programs.
2. The window: ``clients`` closed-loop clients each submit a fresh cloud
   and send the next one as soon as the previous answer is back, through
   the engine's ``submit``/``step``, until ``--seconds`` have passed; the
   requests then in flight are answered and counted. With ``--trace 1``
   the window runs under the JAX profiler, and the harness marks its own
   spans (engine tick, plan build, dispatch, client) in the trace.
3. The check: a sample of the window's answers, drawn from the seed,
   against the family's plain float32 reference on the same coordinates
   and weights, after the engine has been freed.

The last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced); the numbers that decide ``correct`` are printed beside
their limits as the last lines of stderr and, last, under ``checks``.
Without a TPU (or with fewer chips than the cell asks for) it exits 3
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))

import scenes  # noqa: E402
import trace_reduce  # noqa: E402

#: what a family module ``families/<family>.py`` provides: ``arch(cfg)``;
#: ``init_params(arch, seed)``; ``serve(arch, cfg, params, *, bucket,
#: clients, impl)`` -> ``{"engine", "plan_build", "dispatch"}``, the last
#: two the ``(owner, attribute)`` entry points the harness wraps;
#: ``answer(result)``; ``reference(arch, params, coords, feats, bucket, *,
#: precision)``, whose answer lies in the served answer's leading rows;
#: ``cloud_work(arch, coords, peaks)``; ``kernels``. ``control(arch)``
#: (readings.py) and ``rehearse(...)`` (rehearse_compile.py) serve the
#: tools beside the harness.
FAMILY_HOOKS = ("arch", "init_params", "serve", "answer", "reference",
                "cloud_work", "kernels")
#: a family's name becomes a file name
FAMILY_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
#: health counters whose movement means a request was not served as
#: configured: a kernel answered by its oracle, a quarantine, the
#: degradation ladder, a shed, rejected or isolated request
BAD_COUNTERS = ("fallback.", "quarantine.", "serve.degrade.", "serve.shed",
                "serve.isolated", "serve.rejected")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: a request still unanswered this long after the window is lost
DRAIN_LIMIT_S = 120.0
#: one warm-up tick of ``clients`` requests compiles the bucket's
#: executable and the eager plan-build programs; every later tick has the
#: same shapes
WARMUP_TICKS = 1
#: every request's deadline. The engine records the warm-up request's
#: compile time in its per-bucket service estimate
#: (``ServeEngine._note_service``), and the queue sheds a request whose
#: deadline that estimate says it would miss; a deadline far above any
#: compile keeps that defect from shedding requests of the window
DEADLINE_S = 600.0
#: answers of a run compared with the reference: the largest cloud served
#: and others drawn from the seed
CHECK_ANSWERS = 4


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# What the cell is, found by name
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(root: str, workload: str) -> dict:
    """The cell's entry, configuration, traffic and metric lists."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, conf["file"]))
    family = load_family(root, cell["config"], config)
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    # the configuration states its input scale (voxel size, crop); a
    # traffic mix that generates scenes at another scale is refused
    clash = {k: (config[k], v) for k, v in traffic["params"].items()
             if k in config and config[k] != v}
    if clash:
        raise SystemExit(f"traffic {cell['traffic']!r} disagrees with "
                         f"configuration {cell['config']!r}: {clash}")

    def mine(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": cell,
        "config": config,
        "family": family,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "root": root,
    }


def load_module(path: str, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(root: str, config_name: str, config: dict):
    """The module ``bench/families/<family>.py`` that the configuration
    names; a configuration with no family, or one that names a family
    with no module or with hooks missing, is refused."""
    name = config.get("family")
    if not name:
        raise SystemExit(f"configuration {config_name!r} names no model "
                         f'family: its file needs "family": "<name>", '
                         f"with a module bench/families/<name>.py")
    if not isinstance(name, str) or not FAMILY_NAME.fullmatch(name):
        raise SystemExit(f"configuration {config_name!r} names family "
                         f"{name!r}, which is not the plain name of a "
                         f"module under bench/families/")
    path = os.path.join(root, "bench", "families", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"configuration {config_name!r} names family "
                         f"{name!r}, which has no module "
                         f"bench/families/{name}.py")
    mod = load_module(path, "bench_family_", name)
    missing = [h for h in FAMILY_HOOKS if not hasattr(mod, h)]
    if missing:
        raise SystemExit(f"configuration {config_name!r} names family "
                         f"{name!r}, whose module lacks {missing}")
    return mod


def metric_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    return load_module(path, "bench_metric_", name).read


def device_peaks(kind: str) -> dict | None:
    return load_json(os.path.join(HERE, "peaks.json")).get(kind)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def setup_jax(root: str):
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class Compiles:
    """Backend compiles seen since the process started."""

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


class Spans:
    """Harness spans in the profiler's trace, and host time in the plan
    build, around the program entry points that the family's ``serve``
    names: ``plan_build`` and ``dispatch``, each ``(owner, attribute)``."""

    def __init__(self, jax, served: dict):
        self.plan_s = 0.0
        self.plan_n = 0
        self.ann = jax.profiler.TraceAnnotation
        owner, attr = served["plan_build"]
        build = getattr(owner, attr)

        def build_plans(*a, **k):
            t = time.perf_counter()
            with self.ann("bench.plan_build"):
                out = build(*a, **k)
            self.plan_s += time.perf_counter() - t
            self.plan_n += 1
            return out

        self._undo = (owner, attr, build)
        setattr(owner, attr, build_plans)
        owner, attr = served["dispatch"]
        fwd = getattr(owner, attr)

        def forward_fn(*a, **k):
            with self.ann("bench.dispatch"):
                return fwd(*a, **k)

        setattr(owner, attr, forward_fn)

    def close(self) -> None:
        owner, attr, build = self._undo
        setattr(owner, attr, build)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, on_engine=None) -> dict:
    """One run of the cell. ``require_chip=False`` lets a test drive the
    whole run on the CPU; ``on_engine(engine)`` lets it reach into the
    served path."""
    import jax
    root = spec["root"]
    cfg, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    fam = spec["family"]
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        raise NoChip(f"cell {cell['name']} needs {cell['chips']} TPU "
                     f"chip(s); JAX found {len(devs)} {devs[0].platform} "
                     f"device(s)")
    compiles = Compiles(jax)
    from repro.core import plan as planlib
    from repro.kernels.octent import ops as oct_ops
    from repro.kernels.spconv_gemm import ops as sg_ops
    from repro.runtime import guard

    impls = {"search": oct_ops.search_impl(), "gemm": sg_ops.kernel_impl()}
    log(f"resolved impls: {impls}")
    if require_chip and set(impls.values()) != {"pallas"}:
        raise RuntimeError(f"on the chip both kernels must be the compiled "
                           f"Pallas ones, resolved {impls}")
    peaks = device_peaks(devs[0].device_kind)
    if require_chip and peaks is None:
        raise RuntimeError(f"no peaks for device kind "
                           f"{devs[0].device_kind!r} in peaks.json")

    arch = fam.arch(cfg)
    params = jax.block_until_ready(fam.init_params(arch, seed))
    pool = scenes.base_pool(traffic)
    bucket, clients = int(traffic["bucket"]), int(traffic["clients"])
    stream = scenes.requests(pool, traffic, seed)
    served = fam.serve(arch, cfg, params, bucket=bucket, clients=clients,
                       impl=impls["gemm"])
    engine = served["engine"]
    spans = Spans(jax, served)
    if on_engine is not None:
        on_engine(engine)
    sent: dict = {}

    def send(rid: str) -> None:
        base, c, f = next(stream)
        arrays = scenes.padded(c, f, bucket)
        sent[rid] = {"base": base, "coords": c, "feats": f,
                     "t": time.perf_counter()}
        engine.submit(rid, *arrays, deadline_s=DEADLINE_S)

    for tick in range(WARMUP_TICKS):
        n0 = compiles.n
        for k in range(clients):
            send(f"warm{tick}-{k}")
        engine.step()
        log(f"warm-up tick {tick}: {compiles.n - n0} compiles")
    sent.clear()
    setup_s = time.monotonic() - T_START
    log(f"set-up {setup_s:.3f} s")

    h0 = guard.health().snapshot()
    c0, s0 = compiles.n, planlib.mapsearch_call_count()
    n_res0 = len(engine.results)
    trace_dir = os.path.join(root, ".bench_run", "trace")
    spans.plan_s, spans.plan_n = 0.0, 0
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    ann = jax.profiler.TraceAnnotation

    done: dict = {}
    t0 = time.perf_counter()
    t_close = t0 + seconds
    with ann("bench.window"):
        n_sent = 0
        with ann("bench.client"):
            for k in range(clients):
                send(f"r{n_sent}")
                n_sent += 1
        while len(done) < len(sent):
            if time.perf_counter() > t_close + DRAIN_LIMIT_S:
                break
            with ann("bench.engine_step"):
                results = engine.step()
            now = time.perf_counter()
            with ann("bench.client"):
                for r in results:
                    if r.rid not in sent or r.rid in done:
                        continue
                    done[r.rid] = (r, now - sent[r.rid]["t"])
                    if now < t_close:
                        send(f"r{n_sent}")
                        n_sent += 1
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    spans.close()
    window_s = t_end - t0

    moved = {k: v for k, v in guard.health().delta(h0).items()
             if v and k.startswith(BAD_COUNTERS)}
    compiles_in_window = compiles.n - c0
    searches = planlib.mapsearch_call_count() - s0
    ok = [rid for rid, (r, _) in done.items()
          if r.status == "completed" and not r.degraded]
    lat_ms = sorted(1e3 * done[rid][1] for rid in ok)
    lost = len(sent) - len(done)
    log(f"window {window_s:.3f} s: {len(sent)} sent, {len(ok)} completed, "
        f"{lost} unanswered; {compiles_in_window} compiles; bad counters "
        f"{moved or 'none'}; {len(engine.results) - n_res0} engine results")

    mem = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    # -- the check, after the engine is gone ------------------------------
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    n_check = min(CHECK_ANSWERS, len(ok))
    largest = max(ok, key=lambda rid: sent[rid]["coords"].shape[0]) \
        if ok else None
    others = [rid for rid in ok if rid != largest]
    sample = ([largest] if largest else []) + [
        others[i] for i in rng.choice(len(others), max(0, n_check - 1),
                                      replace=False)]
    answers = {rid: fam.answer(done[rid][0]) for rid in sample}
    served_bases = [sent[rid]["base"] for rid in ok]
    plan_s, plan_n = spans.plan_s, spans.plan_n
    del served, engine, done, spans
    gc.collect()
    worst = 0.0
    t_chk = time.perf_counter()
    for rid in sample:
        q = sent[rid]
        want = fam.reference(arch, params, q["coords"], q["feats"], bucket,
                             precision="highest")
        got = np.asarray(answers[rid])[:want.shape[0]]
        err = float(np.abs(got - want).max() / np.abs(want).max())
        if not np.isfinite(err):
            err = float("inf")
        worst = max(worst, err)
    log(f"check of {len(sample)} answers: {time.perf_counter() - t_chk:.3f} s")
    limit = float(cfg["check"]["max_rel_err"])
    checks = {
        "max_rel_err": {"value": worst, "limit": limit},
        "fallbacks": {"value": int(sum(moved.values())), "limit": 0},
        "unanswered": {"value": lost, "limit": 0},
    }
    correct = (worst <= limit and not moved and lost == 0
               and len(sample) >= 1)

    # -- metrics --------------------------------------------------------------
    metrics = {}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    e2e = {"clouds_per_s": len(ok) / window_s, "setup_s": setup_s,
           "latency_p90_ms": float(np.percentile(lat_ms, 90))
           if lat_ms else None}
    breakdown, busy = None, None
    if trace and len(ok) and not plan_n:
        raise RuntimeError("no plan build passed through the harness's "
                           "span around the family's plan-build entry: "
                           "the engine no longer builds plans through it")
    if not trace:
        for m in spec["end_to_end"]:
            if e2e[m["name"]] is not None:
                metrics[m["name"]] = e2e[m["name"]]
    else:
        summary = trace_reduce.summarize(trace_dir, tuple(fam.kernels))
        # every count of the family's cloud_work, summed over the window's
        # clouds; the three every family gives start at 0
        work = {"conv_flops": 0, "model_flops": 0, "conv_min_s": 0.0}
        if peaks is not None:
            per_base = {}
            for b in set(served_bases):
                per_base[b] = fam.cloud_work(arch, pool[b][0], peaks)
            for b in served_bases:
                for k, v in per_base[b].items():
                    work[k] = work.get(k, 0) + v
        ctx = {"trace": summary, "clouds": len(ok), "window_s": window_s,
               "clouds_per_s": e2e["clouds_per_s"], "latency_ms": lat_ms,
               "compiles": compiles_in_window,
               "mapsearch_calls": searches, "plan_build_s": plan_s,
               "plan_builds": plan_n, "work": work, "peaks": peaks}
        for m in spec["per_layer"]:
            v = metric_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = v
        if summary:
            busy = (summary["busy_s"], summary["window_s"])
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if busy is not None:
        device["busy_s"], device["window_s"] = busy
    out = {"correct": bool(correct), "attempted": len(sent),
           "failed": len(sent) - len(ok),
           "metrics": {k: {"value": float(v), "unit": units[k]}
                       for k, v in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cell_spec(ROOT, args.workload)
    setup_jax(ROOT)
    try:
        out = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"run.py: {e}; nothing was run")
        return 3
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
