"""Loop-corrected cost extraction for the roofline analysis.

XLA's ``compiled.cost_analysis()`` counts a while/scan body ONCE, regardless
of trip count (verified empirically; see EXPERIMENTS.md §Dry-run notes).  The
production steps scan over layer groups, so raw module FLOPs/bytes/collective
counts under-report by ~n_layers.  This module compiles each scan-unit body
standalone (tiny HLO, same mesh + shardings) and corrects:

    corrected = module_cost + sum_groups (trip_g - 1) * unit_cost_g

For train steps the scanned backward body includes the remat recompute, so
the unit cost is measured through value_and_grad of the unit (fwd+recompute+
bwd ~= what each backward iteration executes), matching the formula
F_full + (T-1) * F_grad_unit.

Attention inside unit compiles runs in ANALYSIS_DIRECT_ATTENTION mode
(full-score materialization) because the blocked lax.map form has the same
once-counted-body problem.
"""
from __future__ import annotations

import contextlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import distributed as dist
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models import attention as attn_mod
from repro.models import encdec as encdec_mod
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.models.param import abstract_params, param_specs


@contextlib.contextmanager
def _direct_attention():
    prev = attn_mod.ANALYSIS_DIRECT_ATTENTION
    attn_mod.ANALYSIS_DIRECT_ATTENTION = True
    try:
        yield
    finally:
        attn_mod.ANALYSIS_DIRECT_ATTENTION = prev


def _cost_of(compiled) -> dict:
    from repro.launch.hlo import collective_bytes
    cost = compiled.cost_analysis() or {}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(
            collective_bytes(compiled.as_text())["total"]),
    }


def _compile_unit(unit_fn, unit_defs, x_abs, x_sharding, mesh, extra_args=(),
                  extra_shardings=()):
    p_abs = abstract_params(unit_defs)
    p_sh = jax.tree.map(lambda s: steps_lib.named(mesh, s),
                        param_specs(unit_defs))
    jitted = jax.jit(unit_fn, in_shardings=(p_sh, x_sharding)
                     + tuple(extra_shardings))
    lowered = jitted.lower(p_abs, x_abs, *extra_args)
    return _cost_of(lowered.compile())


def _decoder_unit_costs(cfg: ModelConfig, shape, mesh) -> list:
    """[(trip_count, unit_cost_dict)] for each scanned group of the step."""
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1)
    lead, unit, n_rep, tail = tfm.layer_plan(cfg)
    if n_rep <= 1:
        return []
    unit_defs = {f"u{i}": tfm.layer_def(cfg, s, tp, dp)
                 for i, s in enumerate(unit)}
    gb, s = shape.global_batch, shape.seq_len
    bspec = steps_lib.named(mesh, steps_lib.batch_spec(mesh, gb, 2))

    def unit_fwd(p, x, caches=None):
        for i, sig in enumerate(unit):
            c = caches[f"u{i}"] if caches is not None else None
            x, _, _ = tfm.apply_layer(p[f"u{i}"], x, cfg, sig, cache=c,
                                      decode=(shape.kind == "decode"),
                                      pos_offset=0)
        return x

    if shape.kind == "train":
        x_abs = jax.ShapeDtypeStruct((gb, s, cfg.d_model), cfg.compute_dtype)

        def unit_grad(p, x):
            def scalar(p_, x_):
                return jnp.sum(unit_fwd(p_, x_).astype(jnp.float32))
            # return BOTH cotangents: dropping gp would let XLA dead-code-
            # eliminate the weight-gradient matmuls (1/3 of backward FLOPs)
            return jax.grad(scalar, argnums=(0, 1))(p, x)

        with _direct_attention():
            # per scan iteration the step executes one fwd body (forward
            # while loop) AND one remat fwd+bwd body (backward while loop)
            c_fwd = _compile_unit(unit_fwd, unit_defs, x_abs, bspec, mesh)
            c_grad = _compile_unit(unit_grad, unit_defs, x_abs, bspec, mesh)
        cost = {k: c_fwd[k] + c_grad[k] for k in c_fwd}
        return [(n_rep, cost)]

    seq = 1 if shape.kind == "decode" else s
    x_abs = jax.ShapeDtypeStruct((gb, seq, cfg.d_model), cfg.compute_dtype)
    caches_abs = jax.eval_shape(
        lambda: {f"u{i}": tfm._mixer_cache(cfg, sig[0], gb, s)
                 for i, sig in enumerate(unit)})
    c_sh = steps_lib.cache_shardings(caches_abs, mesh, gb)
    with _direct_attention():
        cost = _compile_unit(unit_fwd, unit_defs, x_abs, bspec, mesh,
                             extra_args=(caches_abs,),
                             extra_shardings=(c_sh,))
    return [(n_rep, cost)]


def _encdec_unit_costs(cfg: ModelConfig, shape, mesh) -> list:
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1)
    gb, s = shape.global_batch, shape.seq_len
    bspec = steps_lib.named(mesh, steps_lib.batch_spec(mesh, gb, 2))
    enc_defs = {"u0": tfm.layer_def(cfg, ("enc_attn", "dense"), tp, dp)}
    dec_defs = {"u0": tfm.layer_def(cfg, ("attn", "dense"), tp, dp,
                                    cross=True)}
    out = []

    def enc_fwd(p, x):
        x, _, _ = tfm.apply_layer(p["u0"], x, cfg, ("enc_attn", "dense"))
        return x

    def dec_fwd(p, x, mem):
        x, _, _ = tfm.apply_layer(p["u0"], x, cfg, ("attn", "dense"),
                                  memory=mem)
        return x

    x_abs = jax.ShapeDtypeStruct((gb, s, cfg.d_model), cfg.compute_dtype)
    if shape.kind == "train":
        def enc_grad(p, x):
            return jax.grad(lambda p_, x_: jnp.sum(
                enc_fwd(p_, x_).astype(jnp.float32)), argnums=(0, 1))(p, x)

        def dec_grad(p, x, mem):
            return jax.grad(lambda p_, x_, m_: jnp.sum(
                dec_fwd(p_, x_, m_).astype(jnp.float32)),
                argnums=(0, 1, 2))(p, x, mem)

        with _direct_attention():
            enc_f = _compile_unit(enc_fwd, enc_defs, x_abs, bspec, mesh)
            enc_g = _compile_unit(enc_grad, enc_defs, x_abs, bspec, mesh)
            dec_f = _compile_unit(dec_fwd, dec_defs, x_abs, bspec, mesh,
                                  extra_args=(x_abs,),
                                  extra_shardings=(bspec,))
            dec_g = _compile_unit(dec_grad, dec_defs, x_abs, bspec, mesh,
                                  extra_args=(x_abs,),
                                  extra_shardings=(bspec,))
        out.append((cfg.encoder_layers,
                    {k: enc_f[k] + enc_g[k] for k in enc_f}))
        out.append((cfg.n_layers,
                    {k: dec_f[k] + dec_g[k] for k in dec_f}))
        return out

    if shape.kind == "prefill":
        with _direct_attention():
            out.append((cfg.encoder_layers,
                        _compile_unit(enc_fwd, enc_defs, x_abs, bspec, mesh)))
            out.append((cfg.n_layers,
                        _compile_unit(dec_fwd, dec_defs, x_abs, bspec, mesh,
                                      extra_args=(x_abs,),
                                      extra_shardings=(bspec,))))
        return out

    # decode: self-attn against cache + cross-attn against cached enc K/V
    x1 = jax.ShapeDtypeStruct((gb, 1, cfg.d_model), cfg.compute_dtype)
    caches_abs = jax.eval_shape(
        lambda: attn_mod.init_kv_cache(cfg, gb, s, "attn"))
    cross_abs = jax.eval_shape(
        lambda: attn_mod.init_kv_cache(cfg, gb, s, "attn"))
    c_sh = steps_lib.cache_shardings(caches_abs, mesh, gb)
    cc_sh = steps_lib.cache_shardings(cross_abs, mesh, gb)

    def dec_step(p, x, cache, cross):
        x, _, _ = tfm.apply_layer(p["u0"], x, cfg, ("attn", "dense"),
                                  pos_offset=0, cache=cache, decode=True,
                                  cross_cache=cross)
        return x

    with _direct_attention():
        out.append((cfg.n_layers,
                    _compile_unit(dec_step, dec_defs, x1, bspec, mesh,
                                  extra_args=(caches_abs, cross_abs),
                                  extra_shardings=(c_sh, cc_sh))))
    return out


def corrected_costs(record: dict, cfg: ModelConfig, shape, mesh) -> dict:
    """Apply the (trip-1)*unit correction to a dryrun record's raw costs."""
    groups = (_encdec_unit_costs(cfg, shape, mesh) if cfg.is_enc_dec
              else _decoder_unit_costs(cfg, shape, mesh))
    flops = record["flops_per_device"]
    byts = record["bytes_accessed_per_device"]
    coll = record["collective_bytes_per_device"]["total"]
    per_unit = []
    for trip, cost in groups:
        flops += (trip - 1) * cost["flops"]
        byts += (trip - 1) * cost["bytes"]
        coll += (trip - 1) * cost["collective_bytes"]
        per_unit.append({"trip": trip, **cost})
    return {
        "flops_per_device_corrected": flops,
        "bytes_per_device_corrected": byts,
        "collective_bytes_corrected": coll,
        "units": per_unit,
    }
