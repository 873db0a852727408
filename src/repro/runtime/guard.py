"""Guarded runtime: health counters, backend fallback, adaptive replan.

The hardening layer of DESIGN.md §11, threaded through the whole stack:

  * :class:`RuntimeHealth` — the single stats object every guard event
    lands in (validation repairs, injected faults, fallbacks,
    quarantines, replans, runner recoveries). Flat dotted counter names;
    ``health().snapshot()`` for a JSON-able copy, ``delta()`` for
    per-run accounting.
  * :func:`dispatch` — impl dispatch with a fallback chain for injected
    faults. The primary impl is tried twice (an injected one-shot fault
    recovers on the retry *with the same impl*, which is what keeps
    results bit-identical under the chaos gate); a persistent one
    quarantines the (site, impl, shape-class) for
    ``REPRO_GUARD_COOLDOWN`` calls and walks the fallback chain (the
    bit-exact ``ref`` oracles of kernels/*/ref.py). Any other exception —
    a kernel that fails to lower or compile above all — propagates:
    serving the oracle in its place would hide the device path.
  * :func:`with_replan` — overflow-adaptive replanning. Catches
    :class:`~repro.core.validate.CapacityOverflow` from an eager build
    *and* checks the post-jit ``ConvPlan.overflow`` flag of a built
    plan, then rebuilds with geometrically escalated capacity (bounded
    by ``REPRO_GUARD_REPLAN`` retries). Last-good capacities are
    memoized per key so subsequent steps start at the escalated size —
    the map-search count stays flat across a replaying loop.

Flags (all re-read per call — see runtime/flags.py): REPRO_GUARD_VALIDATE,
REPRO_GUARD_REPLAN, REPRO_GUARD_FALLBACK, REPRO_GUARD_COOLDOWN.
"""
from __future__ import annotations

import contextlib
import logging
import os
import threading

from repro.core import validate
from repro.runtime.fault import InjectedFault

log = logging.getLogger("repro.guard")

#: per-site fallback chains: primary impls -> the bit-exact oracle they
#: fall back to. 'ref' is the XLA twin of the Pallas kernels (tested
#: bit-identical for search; allclose for gemm float accumulation).
FALLBACK_CHAINS = {
    "search": {"pallas": ("ref",), "interpret": ("ref",),
               "sharded": ("ref",), "xla": ("ref",), "ref": ()},
    "gemm": {"pallas": ("ref",), "interpret": ("ref",), "ref": ()},
}


class RuntimeHealth:
    """Flat, thread-safe counter bag for every guard event."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}

    def note(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def delta(self, since: dict) -> dict:
        """Counter increments since a prior :meth:`snapshot` (zero-diff
        names omitted) — per-run accounting on the process-wide bag."""
        now = self.snapshot()
        return {k: v - since.get(k, 0) for k, v in now.items()
                if v != since.get(k, 0)}

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


_HEALTH = RuntimeHealth()


def health() -> RuntimeHealth:
    """The process-wide health stats object."""
    return _HEALTH


def reset_health() -> None:
    """Clear counters *and* quarantine/capacity state (tests)."""
    _HEALTH.reset()
    _QUARANTINE.clear()
    _CAPACITY_HINTS.clear()


@contextlib.contextmanager
def scoped_health():
    """Swap in a fresh :class:`RuntimeHealth` (and empty quarantine /
    capacity-hint state) for the with-block, restoring the previous bag
    and state on exit.

    The process-wide ``_HEALTH`` is deliberately mutable and shared —
    that is what lets every layer note counters without plumbing — but
    it leaks between test cases. Fixtures wrap each case in this scope
    so counters can't bleed: assertions inside the block see only the
    block's own events, and the enclosing process's tallies are intact
    afterwards. Yields the scoped bag (``health()`` returns the same
    object inside the block).
    """
    global _HEALTH
    prev_health = _HEALTH
    prev_quarantine = dict(_QUARANTINE)
    prev_hints = dict(_CAPACITY_HINTS)
    _HEALTH = RuntimeHealth()
    _QUARANTINE.clear()
    _CAPACITY_HINTS.clear()
    try:
        yield _HEALTH
    finally:
        _HEALTH = prev_health
        _QUARANTINE.clear()
        _QUARANTINE.update(prev_quarantine)
        _CAPACITY_HINTS.clear()
        _CAPACITY_HINTS.update(prev_hints)


def dump_health_json(path: str, meta: dict | None = None) -> dict:
    """Write the health snapshot as structured JSON (the ``--health-json``
    flag of launch/train.py and launch/spconv_serve.py).

    The payload is ``{"health": <snapshot>, "meta": <meta or {}>}`` with
    sorted keys, so chaos/serve CI gates assert on counters instead of
    parsing stdout. Returns the payload for in-process callers.
    """
    import json
    payload = {"health": _HEALTH.snapshot(), "meta": dict(meta or {})}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    return payload


# ---------------------------------------------------------------------------
# Flags (re-read per call; documented in runtime/flags.py)
# ---------------------------------------------------------------------------

def validate_policy() -> validate.CloudPolicy | None:
    """REPRO_GUARD_VALIDATE: 'repair' (default) | 'strict' | 'off'."""
    mode = os.environ.get("REPRO_GUARD_VALIDATE", "repair")
    if mode == "off":
        return None
    if mode == "strict":
        return validate.STRICT
    return validate.REPAIR


def replan_retries() -> int:
    """REPRO_GUARD_REPLAN: max capacity escalations (default 6; 0 off)."""
    return int(os.environ.get("REPRO_GUARD_REPLAN", "6"))


def fallback_enabled() -> bool:
    """REPRO_GUARD_FALLBACK: '0' disables the fallback chain."""
    return os.environ.get("REPRO_GUARD_FALLBACK", "1") != "0"


def fallback_cooldown() -> int:
    """REPRO_GUARD_COOLDOWN: calls a quarantined impl sits out (def 32)."""
    return int(os.environ.get("REPRO_GUARD_COOLDOWN", "32"))


# ---------------------------------------------------------------------------
# Backend fallback chain with quarantine + cooldown
# ---------------------------------------------------------------------------

#: (site, impl, shape_key) -> remaining cooldown calls
_QUARANTINE: dict = {}


def _quarantined(qkey) -> bool:
    left = _QUARANTINE.get(qkey, 0)
    if left <= 0:
        return False
    _QUARANTINE[qkey] = left - 1
    return True


def dispatch(site: str, impl: str, fallbacks, call, *, key=()):
    """Run ``call(impl)`` with retry-then-fallback semantics for
    :class:`~repro.runtime.fault.InjectedFault`.

    Args:
      site: failure site name ('search' | 'gemm'), keyed into health
        counters and the fault plan.
      impl: the resolved primary impl.
      fallbacks: ordered impl names to try after the primary fails
        persistently (typically from :data:`FALLBACK_CHAINS`).
      call: ``call(one_impl) -> result`` — must be safe to re-invoke.
      key: shape-class tuple; quarantine is per (site, impl, key) so a
        lowering failure on one shape class does not bench the impl for
        others.

    The primary is attempted twice before falling back: a one-shot
    injected fault recovers with the *same* impl, keeping results
    bit-identical. A persistent one quarantines the primary for
    :func:`fallback_cooldown` subsequent calls and serves the first
    working fallback. Every other exception propagates at once, never
    served by a fallback; with the chain disabled
    (``REPRO_GUARD_FALLBACK=0``) injected faults propagate too.
    """
    if not fallback_enabled():
        return call(impl)
    qkey = (site, impl) + tuple(key)
    err = None
    if _quarantined(qkey):
        _HEALTH.note(f"quarantine.skip.{site}")
    else:
        for attempt in (0, 1):
            try:
                out = call(impl)
                if attempt:
                    _HEALTH.note(f"retry.ok.{site}")
                return out
            except InjectedFault as e:
                err = e
                _HEALTH.note(f"fallback.error.{site}")
                log.warning("%s impl=%r failed (attempt %d): %s",
                            site, impl, attempt + 1, e)
        _QUARANTINE[qkey] = fallback_cooldown()
        _HEALTH.note(f"quarantine.enter.{site}")
        log.warning("%s impl=%r quarantined for %d calls; falling back %r",
                    site, impl, fallback_cooldown(), tuple(fallbacks))
    for fb in fallbacks:
        if fb == impl:
            continue
        try:
            out = call(fb)
            _HEALTH.note(f"fallback.served.{site}")
            _HEALTH.note(f"fallback.served.{site}.{fb}")
            return out
        except InjectedFault as e:
            err = e
            _HEALTH.note(f"fallback.error.{site}")
            log.warning("%s fallback impl=%r failed too: %s", site, fb, e)
    if err is None:
        raise RuntimeError(
            f"{site}: impl {impl!r} quarantined and no fallback available")
    raise err


# ---------------------------------------------------------------------------
# Overflow-adaptive replanning
# ---------------------------------------------------------------------------

#: replan key -> last known-good capacity, so step 2 of a loop starts at
#: the escalated size (and content-hits its cache) instead of re-failing
_CAPACITY_HINTS: dict = {}


def _overflow_flag_set(plan) -> bool:
    """True iff a built plan carries a *concrete* overflow flag that is
    set — the post-jit check. Tracer flags (plan built under an outer
    trace) cannot be inspected here and return False; the in-trace
    escalation path is the eager CapacityOverflow raise at build."""
    flag = getattr(plan, "overflow", None)
    if flag is None:
        return False
    import jax
    try:
        return bool(flag)
    except jax.errors.ConcretizationTypeError:
        return False


def with_replan(build, capacity: int, *, retries: int | None = None,
                growth: int = 2, key=None):
    """Build a plan, escalating capacity geometrically on overflow.

    Args:
      build: ``build(capacity) -> plan``. May raise
        :class:`~repro.core.validate.CapacityOverflow` (the eager path)
        or return a plan whose ``.overflow`` flag is set (the post-jit
        path) — both trigger a rebuild at ``capacity * growth``.
      capacity: starting capacity (e.g. ``max_blocks``). Overridden by
        the memoized last-good capacity for ``key`` when larger.
      retries: max escalations (None: :func:`replan_retries`; 0 makes
        this a plain passthrough that re-raises).
      growth: geometric factor per escalation.
      key: hashable replan identity for the capacity memo (e.g.
        ``('subm3', n_pad, grid_bits)``); None disables memoization.

    Returns ``plan``; raises the final :class:`CapacityOverflow` when
    the retry budget is exhausted.
    """
    retries = replan_retries() if retries is None else retries
    cap = capacity
    if key is not None:
        cap = max(cap, _CAPACITY_HINTS.get(key, 0))
    for attempt in range(retries + 1):
        try:
            plan = build(cap)
        except validate.CapacityOverflow as e:
            if attempt >= retries:
                raise
            _HEALTH.note("replan.overflow")
            nxt = max(cap * growth, int(e.needed or 0))
            log.warning("capacity overflow at %d (%s); replanning at %d",
                        cap, e, nxt)
            cap = nxt
            continue
        if _overflow_flag_set(plan):
            if attempt >= retries:
                raise validate.CapacityOverflow(
                    "post_jit", f"plan overflow flag still set at "
                    f"capacity {cap} after {retries} replans",
                    capacity=cap)
            _HEALTH.note("replan.overflow")
            log.warning("post-jit overflow flag at capacity %d; "
                        "replanning at %d", cap, cap * growth)
            cap *= growth
            continue
        if attempt:
            _HEALTH.note("replan.recovered")
        if key is not None and cap > capacity:
            _CAPACITY_HINTS[key] = cap
        return plan
    raise AssertionError("unreachable")
