"""Mixture-of-Experts FFN (Mixtral top-k routing).

Dispatch is the paper's machinery wearing LM clothes (DESIGN.md §5): the
router assignment table is a rulebook — per-expert contiguous, capacity-
padded gather/scatter streams, exactly like build_tap_tiles builds per-tap
streams for SpConv. Per sequence (vmapped over batch, so it shards cleanly
over the data axes):

    sort token copies by expert -> rank within expert -> slot = e*C + rank
    gather (E, C, D) -> batched expert GEMMs -> weighted scatter-add.

Capacity C = ceil(S * top_k * capacity_factor / E); overflow tokens are
dropped (standard capacity-based MoE), counted in aux metrics.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import common
from repro.runtime.sharding import shard


def init_moe(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": common.normal(ks[0], (d, e), d ** -0.5, jnp.float32),
        "w_gate": common.normal(ks[1], (e, d, f), d ** -0.5, dtype),
        "w_up": common.normal(ks[2], (e, d, f), d ** -0.5, dtype),
        "w_down": common.normal(ks[3], (e, f, d), f ** -0.5, dtype),
    }


def capacity(cfg, seq: int) -> int:
    c = math.ceil(seq * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)       # round up to 8 for tiling


def _dispatch_one(x, logits, k: int, e: int, cap: int):
    """Per-sequence routing. x (S, D), logits (S, E) -> slots + weights."""
    s = x.shape[0]
    top_vals, top_idx = jax.lax.top_k(logits, k)             # (S, k)
    gates = jax.nn.softmax(top_vals, axis=-1)                # Mixtral renorm
    flat_e = top_idx.reshape(-1)                             # (S*k,)
    flat_t = jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    counts = jnp.bincount(se, length=e)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)])[:e]
    rank = jnp.arange(s * k) - jnp.take(starts, se)
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)
    gather_tok = jnp.full((e * cap,), s, jnp.int32).at[slot].set(
        flat_t[order], mode="drop")
    slot_gate = jnp.zeros((e * cap,), jnp.float32).at[slot].set(
        flat_g[order], mode="drop")
    dropped = (~keep).sum()
    return gather_tok, slot_gate, dropped


# 'einsum' — GSPMD decides collective placement (baseline); 'shard_map' —
# expert GEMMs + combine run per model-shard so the TP reduction happens on
# the compact (B, S, D) residual instead of the capacity-expanded
# (B, E, C, D) partials: 1/(top_k*capacity_factor) the bytes, and the
# routed-tensor all-gather disappears (§Perf cell C, iteration C2).
_MOE_IMPL = ["einsum"]


def set_moe_impl(impl: str) -> None:
    assert impl in ("einsum", "shard_map"), impl
    _MOE_IMPL[0] = impl


def _expert_ffn_combine(x_pad, slot_gate, gather_tok, w_gate, w_up, w_down,
                        *, act, s, e):
    """Dispatch gather + expert GEMMs + weighted combine, shard-local under
    shard_map (weights arrive F-sliced; caller psums after the combine).

    Keeping the *gather* inside matters: the backward-pass reduction for the
    replicated input then lands on the compact (B, S, D) cotangent instead
    of the capacity-expanded (B, E, C, D) one — 1/(top_k*capacity_factor)
    the gradient-collective bytes (§Perf C3)."""
    b, _, d = x_pad.shape
    routed = jnp.take_along_axis(x_pad, gather_tok[..., None], axis=1)
    routed = routed.reshape(b, e, -1, d)
    h_g = jnp.einsum("becd,edf->becf", routed, w_gate)
    h_u = jnp.einsum("becd,edf->becf", routed, w_up)
    h = common.activation(h_g, act) * h_u
    y = jnp.einsum("becf,efd->becd", h, w_down)
    y = y.reshape(b, -1, d) * slot_gate[..., None].astype(y.dtype)
    out = jnp.zeros((b, s + 1, d), y.dtype)
    out = jax.vmap(lambda o, yy, t: o.at[t].add(yy, mode="drop"))(
        out, y, gather_tok)[:, :s]
    return out


def moe_ffn(params, x, cfg):
    """x (B, S, D) -> (out, aux_metrics)."""
    from repro.runtime import sharding as rs

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity(cfg, s)
    logits = (x.astype(jnp.float32) @ params["router"])      # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)

    gather_tok, slot_gate, dropped = jax.vmap(
        lambda xx, ll: _dispatch_one(xx, ll, k, e, cap))(x, logits)

    x_pad = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)

    if (_MOE_IMPL[0] == "shard_map" and "model" in rs.active_axes()
            and "model" not in rs.batch_axes()):
        from jax.sharding import PartitionSpec as P

        from jax import shard_map as _shard_map
        from jax.sharding import get_abstract_mesh

        mesh = get_abstract_mesh()
        bspec = rs.resolve("batch", shape=(b,))[0]

        def body(xp_l, gate_l, tok_l, wg_l, wu_l, wd_l):
            out = _expert_ffn_combine(xp_l, gate_l, tok_l, wg_l, wu_l,
                                      wd_l, act=cfg.act, s=s, e=e)
            return jax.lax.psum(out, "model")    # reduce AFTER combine

        sm = _shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, None, None), P(bspec, None),
                      P(bspec, None),
                      P(None, None, "model"), P(None, None, "model"),
                      P(None, "model", None)),
            out_specs=P(bspec, None, None),
            check_vma=False,
        )
        # nested remat: shard_map pins its operands as backward residuals,
        # which defeats the outer layer-level checkpoint (temp +58 GiB/dev,
        # measured in §Perf C3 -> C4); recompute instead.
        out = jax.checkpoint(sm)(x_pad, slot_gate, gather_tok,
                                 params["w_gate"], params["w_up"],
                                 params["w_down"])
    else:
        routed = jnp.take_along_axis(
            x_pad, gather_tok[..., None], axis=1)            # (B, E*C, D)
        routed = routed.reshape(b, e, cap, d)
        routed = shard(routed, "batch", None, None, None)
        h_g = jnp.einsum("becd,edf->becf", routed, params["w_gate"])
        h_u = jnp.einsum("becd,edf->becf", routed, params["w_up"])
        h = shard(common.activation(h_g, cfg.act) * h_u,
                  "batch", None, None, "model")
        y = jnp.einsum("becf,efd->becd", h, params["w_down"])
        y = y.reshape(b, e * cap, d) * slot_gate[..., None].astype(y.dtype)
        out = jnp.zeros((b, s + 1, d), y.dtype)
        out = jax.vmap(lambda o, yy, t: o.at[t].add(yy, mode="drop"))(
            out, y, gather_tok)[:, :s]
    out = shard(out, "batch", None, None)

    # Switch-style load-balance aux: E * sum_e f_e * P_e
    top1 = jnp.argmax(logits, axis=-1)
    f_e = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=(0, 1))
    p_e = probs.mean(axis=(0, 1))
    aux = e * jnp.sum(f_e * p_e)
    metrics = {"moe_aux": aux,
               "moe_drop_frac": dropped.sum() / (b * s * k)}
    return out, metrics
