"""Mixture-of-Experts FFN: top-k routing with sort-based dispatch.

TPU-native design decision (DESIGN.md §7): instead of the GShard dense
one-hot dispatch einsum — whose [tokens, E, capacity] tensors explode for
DeepSeek-V3's 256 experts — we use sort-based dispatch (argsort of expert
assignments + capacity-bounded scatter/gather), the MaxText-style approach.
Expert FLOPs in the compiled HLO then reflect the *active* (top-k) compute,
which is what the roofline's MODEL_FLOPS ratio wants to see.

Supports: top-k normalized combine weights, capacity factor with token
dropping, shared (always-on) experts (DeepSeek), and the switch-style
load-balance auxiliary loss.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import distributed as dist
from repro.models.config import ModelConfig
from repro.models.layers import dense, dense_def, mlp, mlp_def
from repro.models.param import ParamDef, divisible


def expert_capacity(cfg: ModelConfig, seq: int) -> int:
    cap = math.ceil(cfg.moe_top_k * seq * cfg.capacity_factor
                    / cfg.moe_num_experts)
    return max(4, ((cap + 3) // 4) * 4)


def moe_def(cfg: ModelConfig, tp: int = 16, dp: int = 16):
    e, d, f = cfg.moe_num_experts, cfg.d_model, cfg.expert_d_ff
    e_ax = "data" if divisible(e, dp) else None
    d_ax = None if e_ax == "data" else ("data" if divisible(d, dp) else None)
    f_ax = "model" if divisible(f, tp) else None
    defs = {
        "router": ParamDef((d, e), init="scaled", spec=P(None, None),
                           dtype=jnp.float32, fan_in=d),
        # up+gate fused on an unsharded axis (§Perf it.2): one d_ein
        # all-reduce in backward instead of two
        "wi": ParamDef((e, d, 2, f), init="scaled",
                       spec=P(e_ax, d_ax, None, f_ax),
                       dtype=cfg.param_dtype, fan_in=d),
        "wo": ParamDef((e, f, d), init="scaled", spec=P(e_ax, f_ax, d_ax),
                       dtype=cfg.param_dtype, fan_in=f),
    }
    if cfg.moe_shared_experts:
        defs["shared"] = mlp_def(cfg, d_ff=cfg.expert_d_ff
                                 * cfg.moe_shared_experts, tp=tp)
    return defs


def _dispatch_indices(expert_id: jax.Array, capacity: int, num_experts: int):
    """expert_id: [A] flat assignments. Returns (slot[A], keep[A]).

    slot = expert * capacity + rank-within-expert (rank by token order).
    """
    a = expert_id.shape[0]
    order = jnp.argsort(expert_id, stable=True)          # sorted assignment ids
    sorted_eid = expert_id[order]
    # rank within expert group = position - first index of that expert value
    first = jnp.searchsorted(sorted_eid, sorted_eid, side="left")
    rank_sorted = jnp.arange(a) - first
    rank = jnp.zeros((a,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < capacity
    slot = jnp.where(keep, expert_id * capacity + rank, num_experts * capacity)
    return slot, keep


def moe_apply(p, x: jax.Array, cfg: ModelConfig):
    """x: [B, S, D] -> (y [B,S,D], aux_loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    cap = expert_capacity(cfg, s)
    ct = cfg.compute_dtype

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)               # [B,S,K]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # switch-style load-balance loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_e[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    mean_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * mean_probs)

    def dispatch_one(xb, eid):
        # xb [S,D]; eid [S,K] -> (expert_in [E,C,D], slot [S*K], keep [S*K])
        flat_e = eid.reshape(-1)                          # [S*K] (s-major)
        slot, keep = _dispatch_indices(flat_e, cap, e)
        tok = jnp.repeat(jnp.arange(s), k)
        buf = jnp.zeros((e * cap + 1, d), ct)
        buf = buf.at[slot].add(jnp.where(keep[:, None], xb[tok].astype(ct), 0))
        return buf[:e * cap].reshape(e, cap, d), slot, keep

    def combine_one(eout, wgt, slot, keep):
        # eout [E,C,D] -> y [S,D]
        tok = jnp.repeat(jnp.arange(s), k)
        flat_out = eout.reshape(e * cap, d)
        contrib = jnp.where(keep[:, None],
                            flat_out[jnp.minimum(slot, e * cap - 1)]
                            * wgt.reshape(-1)[:, None].astype(ct), 0)
        return jnp.zeros((s, d), ct).at[tok].add(contrib)

    # §Perf note: the dispatch/combine scatters must run as *local* per-
    # batch-shard ops.  Left to auto-SPMD, XLA replicates the scatter across
    # the data axis (batch sharding lost), which then drags the expert
    # matmuls into replicated-batch form with ~100 GB/layer of activation
    # all-reduces (measured; see EXPERIMENTS.md §Perf mixtral iteration 1).
    # Wrapping them in shard_map over the batch axes pins them local; the
    # expert einsums stay in auto-SPMD so XLA picks weight-gather sharding.
    mesh = dist.active_mesh()
    if mesh is not None:
        baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        bsz_total = 1
        for a in baxes:
            bsz_total *= mesh.shape[a]
        if b % bsz_total:
            baxes = ()
        bspec = P(baxes if baxes else None)
        rep = P(*([None] * 2))

        dispatch = jax.shard_map(
            jax.vmap(dispatch_one),
            mesh=mesh,
            in_specs=(P(bspec[0], None, None), P(bspec[0], None, None)),
            out_specs=(P(bspec[0], None, None, None),
                       P(bspec[0], None), P(bspec[0], None)),
            check_vma=False)
        combine = jax.shard_map(
            jax.vmap(combine_one),
            mesh=mesh,
            in_specs=(P(bspec[0], None, None, None), P(bspec[0], None, None),
                      P(bspec[0], None), P(bspec[0], None)),
            out_specs=P(bspec[0], None, None),
            check_vma=False)
        ein, slot, keep = dispatch(x, top_e)
    else:
        ein, slot, keep = jax.vmap(dispatch_one)(x, top_e)
        combine = jax.vmap(combine_one)

    # (§Perf it.4 tried sharding the capacity axis over 'model' here to
    # localize the expert matmuls — REFUTED: measured collective bytes rose
    # 2.3x because the constraint forced resharding at the shard_map
    # boundaries instead of the hoped-for weight gathers. Reverted.)
    h2 = jnp.einsum("becd,edgf->becgf", ein, p["wi"].astype(ct))
    h = jax.nn.silu(h2[..., 0, :]) * h2[..., 1, :]
    eout = jnp.einsum("becf,efd->becd", h, p["wo"].astype(ct))
    eout = dist.constrain(eout, (dist.batch_logical(), None, None, None))
    y = combine(eout, top_w, slot, keep)

    if cfg.moe_shared_experts:
        y = y + mlp(p["shared"], x, cfg)
    return y.astype(x.dtype), aux
