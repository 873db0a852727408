"""Ways to break the served path underneath a run, for the checks that
``correct`` must fail (test_bench_faults.py, readings.py). The control,
the family's reference in the program's place, is the family's own
``control`` hook.

Each is an ``on_engine(engine)`` hook for :func:`run.run_cell`; the ones
that patch a module attribute return a callable that undoes it.
"""
from __future__ import annotations


def alter_answer(delta: float = 1e-2):
    """One logit of every answer moved by ``delta`` where it is produced."""
    def hook(engine):
        fwd = engine._forward_fn

        def forward_fn(*a, **k):
            return fwd(*a, **k).at[0, 0].add(delta)
        engine._forward_fn = forward_fn
    return hook


def swap_answers(engine):
    """The first two answers of every tick handed to each other's client."""
    step = engine.step

    def swapped():
        res = step()
        done = [r for r in res if r.status == "completed"]
        if len(done) >= 2:
            done[0].rid, done[1].rid = done[1].rid, done[0].rid
        return res
    engine.step = swapped


def drop_tap(tap: int = 0):
    """The map search loses one kernel tap: no neighbour is found there.
    Returns ``(hook, undo)``."""
    from repro.kernels.octent import ops
    build = ops.build_kmap

    def build_kmap(*a, **k):
        kmap, n_blocks = build(*a, **k)
        return kmap.at[:, tap].set(-1), n_blocks

    def hook(engine):
        ops.build_kmap = build_kmap

    def undo():
        ops.build_kmap = build
    return hook, undo
