"""Reduce a profiler trace to the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``:

* device ops: the events of each ``/device:*`` plane's ``XLA Ops`` line;
* harness spans: the events named ``bench.*`` on the host plane, which
  ``run.py`` writes with ``jax.profiler.TraceAnnotation``.

Everything is clipped to the ``bench.window`` span. Busy time is the
union of the device op intervals (averaged over the devices); each idle
gap is attributed to the innermost harness span open at its midpoint.
The interval arithmetic works on plain ``(start_ns, end_ns)`` lists and
is tested on synthetic events.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"


def union(intervals) -> list:
    """Merged, sorted, non-overlapping ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi) -> list:
    """The idle intervals of ``[lo, hi)`` not covered by merged ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def label_at(spans, t) -> str:
    """Name of the innermost span (latest start) open at time ``t``."""
    best, start = None, None
    for name, s, e in spans:
        if s <= t < e and (start is None or s >= start):
            best, start = name, s
    return best or "none"


def attribute(idle, spans) -> dict:
    """Idle nanoseconds per label of the span open at each gap's middle."""
    out: dict = {}
    for s, e in idle:
        k = label_at(spans, (s + e) / 2)
        out[k] = out.get(k, 0) + (e - s)
    return out


def op_family(name: str) -> str:
    """An op's name without XLA's numeric suffixes (``fusion.12``)."""
    return re.sub(r"(\.\d+)+$", "", name)


def reduce(ops_by_device: dict, spans: list, kernels=()) -> dict:
    """The summary the metric readers take.

    ``ops_by_device``: device name -> list of ``(op name, start, end)``;
    ``spans``: ``(name, start, end)`` harness spans (one ``bench.window``
    among them). Times in ns. Returns seconds.
    """
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if not win or not ops_by_device:
        return {}
    lo, hi = win[0]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    busy_total, idle_by = 0.0, {}
    per_op: dict = {}
    kernel_ns = {k: 0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    for ops in ops_by_device.values():
        iv = clip([(s, e) for _, s, e in ops], lo, hi)
        busy = union(iv)
        busy_total += sum(e - s for s, e in busy)
        for k, v in attribute(gaps(busy, lo, hi), inner).items():
            idle_by[k] = idle_by.get(k, 0) + v
        for n, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            fam = op_family(n)
            per_op[fam] = per_op.get(fam, 0) + d
            for k in kernels:
                if k in n:
                    kernel_ns[k] += d
                    kernel_calls[k] += 1
    n_dev = len(ops_by_device)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(idle_by.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "devices": n_dev,
        "kernel_s": {k: v / n_dev / 1e9 for k, v in kernel_ns.items()},
        "kernel_calls": kernel_calls,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in top],
        "idle_gaps": [[k.removeprefix("bench."), v / n_dev / 1e9]
                      for k, v in idle],
    }


def load(trace_dir: str, kernels=()):
    """``(ops_by_device, spans)`` from the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {}, []
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    ops_by_device, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                ops_by_device[plane.name] = [
                    (_op_name(ev, kernels), ev.start_ns,
                     ev.start_ns + ev.duration_ns)
                    for ev in lines[OPS_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith("bench."))
    return ops_by_device, spans


def _op_name(ev, kernels) -> str:
    """An op's name; a kernel's name where the op is named for its call
    and the kernel shows only in the op's HLO text."""
    if any(k in ev.name for k in kernels):
        return ev.name
    for key, v in ev.stats:
        if key in ("long_name", "hlo_op", "tf_op"):
            hit = next((k for k in kernels if k in str(v)), None)
            if hit:
                return hit
    return ev.name


def summarize(trace_dir: str, kernels=()) -> dict:
    ops, spans = load(trace_dir, kernels)
    return reduce(ops, spans, kernels)
