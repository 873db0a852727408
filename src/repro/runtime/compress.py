"""Gradient compression for the inter-pod DP all-reduce.

Inter-pod links are the thinnest in the system; int8 + per-tensor scale
quantization cuts gradient all-reduce bytes 4x (vs fp32) / 2x (vs bf16) at
the cost of one extra abs-max reduction. Exposed as a shard_map collective
(:func:`compressed_psum_mean`) used by train drivers when
``grad_compress='int8'``; error is bounded by scale/127 per element and is
validated against the exact mean in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_int8(x: jnp.ndarray):
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Mean over ``axis`` of int8-compressed tensors (inside shard_map).

    Each participant quantizes locally; int32 accumulation of int8 payloads
    is exact, so the only error is local quantization. Scales are maxed
    across the axis so the shared codebook is valid everywhere.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12) / 127.0
    scale = jax.lax.pmax(scale, axis)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    total = jax.lax.psum(q.astype(jnp.int32), axis)
    n = jax.lax.psum(jnp.ones((), jnp.int32), axis)
    return (total.astype(jnp.float32) * scale / n).astype(x.dtype)


def grad_allreduce_compressed(grads, mesh, axis: str = "pod"):
    """Apply compressed mean-all-reduce to a grad pytree over ``axis``.

    The grads enter replicated over all axes except ``axis`` (the DP axis
    being compressed); everything else is left to pjit."""
    from jax.sharding import PartitionSpec as P

    def per_shard(g):
        return jax.tree.map(lambda a: compressed_psum_mean(a, axis), g)

    spec = jax.tree.map(lambda _: P(), grads,
                        is_leaf=lambda x: hasattr(x, "shape"))
    from jax import shard_map
    fn = shard_map(per_shard, mesh=mesh, in_specs=(spec,),
                   out_specs=spec, check_vma=False)
    return fn(grads)
