"""Ways to break the served path underneath a run, for the checks that
``correct`` must fail (test_bench_faults.py, readings.py).

Each is an ``on_engine(engine)`` hook for :func:`run.run_cell`; the ones
that patch a module attribute return a callable that undoes it.
"""
from __future__ import annotations

import numpy as np

import geometry
import reference


def control(arch: reference.Arch):
    """The reference, at the next precision down, in the program's place:
    every request of the run is answered by it."""
    def hook(engine):
        def forward_fn(params, st, plans):
            import jax.numpy as jnp
            valid = np.asarray(st.valid)
            n = int(valid.sum())
            coords = np.asarray(st.coords)[:n]
            feats = np.asarray(st.feats)[:n]
            out = reference.forward(
                arch, params, feats, geometry.hierarchy(coords, len(arch.enc)),
                valid.shape[0], precision="bf16x3")
            full = np.zeros((valid.shape[0], out.shape[1]), np.float32)
            full[:n] = out
            return jnp.asarray(full)
        engine._forward_fn = forward_fn
    return hook


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
    run_batch = engine._execute_batch

    def execute_batch(reqs):
        res = run_batch(reqs)
        done = [r for r in res if r.logits is not None]
        if len(done) >= 2:
            done[0].logits, done[1].logits = done[1].logits, done[0].logits
        return res
    engine._execute_batch = execute_batch


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
