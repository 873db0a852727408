"""Split a traced window's device time by the program's own phases.

The program marks its phases with host spans named ``serve.*`` and
``plan.*`` (``jax.profiler.TraceAnnotation`` in ``launch/spconv_serve.py``,
a model's ``build_plans`` and ``core/plan.py``). This module reads
the ``.xplane.pb`` that ``run.py`` leaves under
``<checkout>/.bench_run/trace`` while the per-layer readers run, and puts
each device op down to the span that launched it:

* a v5e's trace names a device op by its HLO instruction and carries no
  op metadata, so the program's ``jax.named_scope`` scopes (which label
  the ops in xprof's views) cannot be read here; the launch can. The
  device runs executables in the order the host launched them, so the
  k-th run on a device's ``XLA Modules`` line belongs to the k-th
  ``PJRT_LoadedExecutable_Execute`` event of the host; where the two
  counts differ nothing is attributed;
* an op belongs to the run whose interval holds its start, and takes the
  innermost program span open when that run was launched (``none``
  outside every program span). The kernels are found by name as
  ``trace_reduce`` finds them;
* each device idle gap goes to the innermost program span open at its
  middle.

Everything is clipped to the harness's ``bench.window`` span and averaged
over the devices; device time per label is the union of its ops'
intervals. A trace of a program without these spans reduces to no
phases, and the readers that use it return None. The interval arithmetic
works on plain tuples and is tested on synthetic events.
"""
from __future__ import annotations

import bisect
import glob
import os

import trace_reduce

SPAN_PREFIXES = ("plan.", "serve.")
#: host event of one executable launch, and the device line of its run
LAUNCH = "PJRT_LoadedExecutable_Execute"
RUNS_LINE = "XLA Modules"
#: the span the forward executable is launched in
FORWARD = "serve.dispatch"

_memo: dict = {}


def label_ops(runs, ops, launches, spans) -> list | None:
    """``(start, end, label, kernel)`` per op of one device, or None
    where the device's runs and the host's launches do not pair up.

    ``runs``: ``(start, end)`` executable runs; ``ops``: ``(start, end,
    kernel)``; ``launches``: launch times; ``spans``: ``(name, start,
    end)`` program spans.
    """
    if len(runs) != len(launches):
        return None
    runs = sorted(runs)
    starts = [s for s, _ in runs]
    labels = [trace_reduce.label_at(spans, t) for t in sorted(launches)]
    out = []
    for s, e, kernel in ops:
        k = bisect.bisect_right(starts, s) - 1
        inside = k >= 0 and s < runs[k][1]
        out.append((s, e, labels[k] if inside else "none", kernel))
    return out


def _span(intervals) -> int:
    return sum(e - s for s, e in trace_reduce.union(intervals))


def reduce(devices: dict, launches: list, spans: list, window) -> dict:
    """The phases of one traced window.

    ``devices``: device -> ``(runs, ops)`` as :func:`label_ops` takes
    them; ``launches``: host launch times; ``spans``: ``(name, start,
    end)`` program spans; ``window``: ``(start, end)``. Times in ns.
    Returns seconds, or ``{}`` without a window or devices.
    """
    if window is None or not devices:
        return {}
    lo, hi = window
    n_dev = len(devices)
    picks = {
        "tiles": lambda lab, k: lab == "plan.tiles",
        "search": lambda lab, k: lab == "plan.search",
        "forward_xla": lambda lab, k: lab == FORWARD and k is None,
        "attributed": lambda lab, k: k is not None
        or lab.startswith(SPAN_PREFIXES),
    }
    acc = dict.fromkeys(("busy", *picks), 0)
    label_ns: dict = {}
    idle_ns: dict = {}
    paired = True
    for runs, ops in devices.values():
        labelled = label_ops(runs, ops, launches, spans)
        if labelled is None:
            paired = False
            labelled = [(s, e, "unpaired", k) for s, e, k in ops]
        labelled = [(max(s, lo), min(e, hi), lab, k)
                    for s, e, lab, k in labelled if min(e, hi) > max(s, lo)]
        busy = trace_reduce.union((s, e) for s, e, _, _ in labelled)
        acc["busy"] += sum(e - s for s, e in busy)
        for name, pick in picks.items():
            acc[name] += _span((s, e) for s, e, lab, k in labelled
                               if pick(lab, k))
        for lab in {lab for _, _, lab, _ in labelled}:
            label_ns[lab] = label_ns.get(lab, 0) + _span(
                (s, e) for s, e, x, _ in labelled if x == lab)
        idle = trace_reduce.gaps(busy, lo, hi)
        for k, v in trace_reduce.attribute(idle, spans).items():
            idle_ns[k] = idle_ns.get(k, 0) + v

    def seconds(d):
        return {k: v / n_dev / 1e9
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

    out = {k + "_s": v / n_dev / 1e9 for k, v in acc.items()}
    out.update(
        window_s=(hi - lo) / 1e9, devices=n_dev, paired=paired,
        spanned=bool(spans), label_s=seconds(label_ns),
        idle_s=seconds(idle_ns),
        plan_idle_s=sum(v for k, v in idle_ns.items()
                        if k.startswith("plan.")) / n_dev / 1e9)
    return out


def kernel_of(ev, kernels) -> str | None:
    """The kernel a device op runs, found as ``trace_reduce`` finds it
    (in the op's name or its HLO text), or None."""
    name = trace_reduce._op_name(ev, kernels)
    return next((k for k in kernels if k in name), None)


def load(trace_dir: str, kernels=()):
    """``(devices, launches, program spans, window)`` from the newest
    ``.xplane.pb`` under ``trace_dir``, as :func:`reduce` takes them."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {}, [], [], None
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, launches, spans, window = {}, [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if trace_reduce.OPS_LINE not in lines:
                continue
            runs = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in lines[RUNS_LINE].events] \
                if RUNS_LINE in lines else []
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                    kernel_of(ev, kernels))
                   for ev in lines[trace_reduce.OPS_LINE].events]
            devices[plane.name] = (runs, ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == LAUNCH:
                        launches.append(ev.start_ns)
                    elif ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif ev.name == trace_reduce.WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    return devices, launches, spans, window


def trace_dir_of(reader: str) -> str:
    """The harness's trace directory in the checkout that holds the
    per-layer reader ``<checkout>/bench/metrics/<name>.py``."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader))))
    return os.path.join(root, ".bench_run", "trace")


def summary(ctx: dict, reader: str) -> dict:
    """The phases of the run whose per-layer readers are running (read
    once per trace file); ``{}`` in a run without a device trace."""
    if not ctx.get("trace"):
        return {}
    trace_dir = trace_dir_of(reader)
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {}
    path = max(files, key=os.path.getmtime)
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if key not in _memo:
        kernels = tuple(ctx["trace"].get("kernel_s", {}))
        _memo.clear()
        _memo[key] = reduce(*load(trace_dir, kernels))
    return _memo[key]


def per_cloud_ms(ctx: dict, reader: str, key: str):
    """``key`` of the summary in ms per completed cloud, or None where
    the program marks no span or launches and runs do not pair up."""
    s = summary(ctx, reader)
    if not (s.get("paired") and s.get("spanned")) or not ctx["clouds"]:
        return None
    return 1e3 * s[key] / ctx["clouds"]
