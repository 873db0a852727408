"""Parameter / batch / cache sharding rules (DESIGN.md §4).

Rules are name-keyed on the last path component and rank-generic; the
divisibility filter in runtime.sharding.resolve silently replicates dims the
mesh extent does not divide (8 KV heads or vocab 50280 on a 16-way model
axis), so one rule table covers every architecture and both meshes.
"""
from __future__ import annotations

import jax
from jax import set_mesh
from jax.sharding import NamedSharding

from repro.runtime import sharding as rs

# weight matrices whose LAST dim is the TP-sharded output features
_LAST = {"wq", "wk", "wv", "w_gate", "w_up", "lm_head", "pred_head",
         "in_proj", "conv_w", "conv_b", "w_x", "w_gate_branch", "proj_in",
         "frontend_proj", "norm_w", "lam", "w"}
# weight matrices whose SECOND-TO-LAST dim is the TP-sharded input features
_SECOND_LAST = {"wo", "w_down", "out_proj", "w_out", "proj_out"}
# token/state caches: name -> logical dims. Two layouts for attention KV:
#   'kv'  (baseline) — shard the kv-head dim; falls back to REPLICATED when
#          kv_heads < |model| (the GQA trap measured in §Perf cell A);
#   'ctx' — context parallelism: shard the capacity dim over 'model';
#          attention reduces with one tiny psum instead of gathering the
#          cache. §Perf default after iteration A1.
_CACHE_RULES_KV = {
    "k": (None, "batch", None, "model", None),
    "v": (None, "batch", None, "model", None),
}
_CACHE_RULES_CTX = {
    "k": (None, "batch", "model", None, None),
    "v": (None, "batch", "model", None, None),
}
_CACHE_RULES = {
    "conv": (None, "batch", None, "model"),
    "ssm": (None, "batch", "model", None, None),
    "rec_h": (None, None, "batch", "model"),
    "rec_conv": (None, None, "batch", None, "model"),
    "tail_h": (None, "batch", "model"),
    "tail_conv": (None, "batch", None, "model"),
}


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
        if hasattr(entry, "name"):
            return str(entry.name)
    return ""


def _param_dims(name: str, rank: int, strategy: str = "tp"):
    if strategy == "pure_dp":           # replicate everything (§Perf cell B)
        return (None,) * rank
    if rank <= 1:                       # scales/biases: replicate
        return (None,) * rank
    if name == "embed":
        return ("model",) + (None,) * (rank - 1)
    if name in _LAST:
        return (None,) * (rank - 1) + ("model",)
    if name in _SECOND_LAST:
        return (None,) * (rank - 2) + ("model", None)
    return (None,) * rank


def param_shardings(abstract_params, mesh, strategy: str = "tp"):
    """NamedSharding pytree for a parameter tree (also fits AdamW m/v)."""
    with set_mesh(mesh):
        def one(path, leaf):
            dims = _param_dims(_leaf_name(path), len(leaf.shape), strategy)
            spec = rs.resolve(*dims, shape=tuple(leaf.shape))
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map_with_path(one, abstract_params)


def opt_state_shardings(abstract_opt, mesh, strategy: str = "tp"):
    """m/v mirror params; count replicated. abstract_opt from eval_shape.

    pure_dp shards m/v over the whole mesh on the first divisible dim
    (ZeRO-1): params stay replicated but optimizer state is 1/N per chip.
    """
    with set_mesh(mesh):
        def one(path, leaf):
            rank = len(leaf.shape)
            if strategy == "pure_dp" and rank >= 1:
                all_axes = tuple(mesh.axis_names)
                for i in range(rank):
                    spec = rs.resolve(
                        *((None,) * i + (all_axes,) + (None,) * (rank - i - 1)),
                        shape=tuple(leaf.shape))
                    if spec[i] is not None:
                        return NamedSharding(mesh, spec)
                return NamedSharding(mesh, rs.resolve(*(None,) * rank))
            dims = _param_dims(_leaf_name(path), rank, strategy)
            spec = rs.resolve(*dims, shape=tuple(leaf.shape))
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map_with_path(one, abstract_opt)


def batch_shardings(abstract_batch, mesh):
    """Model inputs: leading dim is the global batch (set_batch_axes)."""
    with set_mesh(mesh):
        def one(path, leaf):
            dims = ("batch",) + (None,) * (len(leaf.shape) - 1)
            spec = rs.resolve(*dims, shape=tuple(leaf.shape))
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map_with_path(one, abstract_batch)


def cache_shardings(abstract_cache, mesh, kv_layout: str = "kv"):
    rules = dict(_CACHE_RULES)
    rules.update(_CACHE_RULES_CTX if kv_layout == "ctx" else _CACHE_RULES_KV)
    with set_mesh(mesh):
        def one(path, leaf):
            name = _leaf_name(path)
            rank = len(leaf.shape)
            dims = rules.get(name, (None,) * rank)
            dims = dims[:rank] if len(dims) >= rank else (None,) * rank
            spec = rs.resolve(*dims, shape=tuple(leaf.shape))
            return NamedSharding(mesh, spec)

        return jax.tree_util.tree_map_with_path(one, abstract_cache)
