"""Pipeline parallelism over the ``pod`` axis (GPipe schedule, shard_map).

At 1000+ nodes the per-layer TP collectives must stay inside a pod; the
inter-pod links carry either gradient all-reduce (DP) or activations (PP).
This module provides the PP option: layers are split into S = |pod| stages
(params stacked on a leading stage axis, sharded over 'pod'); microbatches
flow stage-to-stage via collective_permute with the classic GPipe bubble.

The schedule runs M + S - 1 ticks for M microbatches; each tick every stage
computes its resident microbatch then hands it downstream. Used by the
multi-pod dry-run variant and validated numerically in tests (8 host
devices, subprocess) against the unpipelined reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_params, x_mb, stage_fn, *, mesh, axis: str = "pod",
                   extra_spec=P()):
    """Run a GPipe pipeline.

    stage_params: pytree with leading stage axis S (sharded over ``axis``).
    x_mb: (M, mb, ...) microbatched input, replicated over ``axis``.
    stage_fn(params_slice, x) -> y, applied S times in sequence overall.
    Returns (M, mb, ...) outputs of the last stage.
    """
    s = mesh.shape[axis]
    m = x_mb.shape[0]
    n_ticks = m + s - 1

    def per_stage(params, xs):
        # params: stage-local slice (leading axis 1); xs: (M, mb, ...)
        params = jax.tree.map(lambda a: a[0], params)
        stage_id = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs)                     # outputs accumulator
        carry_in = jnp.zeros_like(xs[0])

        def tick(state, t):
            carry, buf = state
            # stage 0 ingests microbatch t; others use the handed-off carry
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(stage_id == 0, xs[mb_idx], carry)
            y = stage_fn(params, x_in)
            # live iff this stage holds microbatch (t - stage_id) in [0, M)
            live = (t >= stage_id) & (t - stage_id < m)
            out_idx = jnp.clip(t - stage_id, 0, m - 1)
            buf = jnp.where(live,
                            buf.at[out_idx].set(y),
                            buf)
            # hand off downstream (ring; the wraparound write is ignored)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            return (nxt, buf), None

        (carry_in, buf), _ = jax.lax.scan(
            tick, (carry_in, buf), jnp.arange(n_ticks))
        # only the last stage's buffer is meaningful; broadcast via masked
        # psum (a one-to-all hand-back is not a permutation)
        return jax.lax.psum(
            jnp.where(stage_id == s - 1, buf, jnp.zeros_like(buf)), axis)

    in_specs = (jax.tree.map(lambda _: P(axis), stage_params,
                             is_leaf=lambda x: hasattr(x, "shape")),
                extra_spec)
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                       out_specs=extra_spec, check_vma=False)
    return fn(stage_params, x_mb)


def stack_stages(layer_params, n_stages: int):
    """Regroup per-layer stacked params (L, ...) into (S, L/S, ...)."""
    def regroup(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])

    return jax.tree.map(regroup, layer_params)
