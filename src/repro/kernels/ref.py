"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

def ota_aggregate_ref(g: jax.Array, s: jax.Array, z: jax.Array,
                      noise_scale: jax.Array) -> jax.Array:
    """out = sum_m s_m g_m + noise_scale * z  (g: [N, D]).

    Accumulates in f32 and casts on write, matching the Pallas kernel (and
    core.ota.weighted_sum): casting s to a low-precision g dtype before the
    reduction would lose coefficient precision.
    """
    acc = jnp.sum(g.astype(jnp.float32) * s[:, None].astype(jnp.float32),
                  axis=0)
    return (acc + noise_scale.astype(jnp.float32)
            * z.astype(jnp.float32)).astype(g.dtype)


def ota_round_step_ref(g: jax.Array, s: jax.Array, z: jax.Array,
                       noise_scale: jax.Array, params: jax.Array,
                       eta: jax.Array,
                       q_scale: Optional[jax.Array] = None) -> jax.Array:
    """Fused OTA round step on flat arrays (g: [N, D] wire-dtype grads,
    params: [D] f32):

        ghat = sum_m qs_m g_m s_m + noise_scale * z
        out  = params - eta * ghat

    ``q_scale`` is the per-device symmetric dequantization scale of a
    quantized uplink (None for f32/bf16 — the f32 cast dequantizes those).
    Accumulates in f32 end-to-end and casts once on write, matching the
    Pallas kernel.  With an f32 uplink the aggregation expression is
    ``ota_aggregate_ref`` verbatim, which is what keeps the fused path
    bitwise with the unfused flat path.
    """
    gf = g.astype(jnp.float32)
    if q_scale is not None:
        gf = gf * q_scale[:, None].astype(jnp.float32)
    acc = jnp.sum(gf * s[:, None].astype(jnp.float32), axis=0)
    ghat = acc + noise_scale.astype(jnp.float32) * z.astype(jnp.float32)
    return (params.astype(jnp.float32)
            - eta.astype(jnp.float32) * ghat).astype(params.dtype)


def ssd_ref(x: jax.Array, dt: jax.Array, a_neg: jax.Array, b_mat: jax.Array,
            c_mat: jax.Array) -> jax.Array:
    """Sequential SSD recurrence (the mathematical definition):

        S_t = exp(dt_t a) S_{t-1} + dt_t B_t (x) x_t
        y_t = C_t . S_t

    x: [B,S,H,P]; dt: [B,S,H]; a_neg: [H]; b_mat/c_mat: [B,S,G,N].
    """
    bsz, s, h, p_dim = x.shape
    g = b_mat.shape[2]
    n_dim = b_mat.shape[3]
    rep = h // g
    bh = jnp.repeat(b_mat, rep, axis=2) if rep > 1 else b_mat
    ch = jnp.repeat(c_mat, rep, axis=2) if rep > 1 else c_mat

    def step(state, inp):
        xt, dtt, bt, ct = inp                       # [B,H,P],[B,H],[B,H,N],..
        da = jnp.exp(dtt * a_neg[None, :])          # [B,H]
        state = state * da[..., None, None] + jnp.einsum(
            "bh,bhn,bhp->bhpn", dtt, bt, xt)
        y = jnp.einsum("bhn,bhpn->bhp", ct, state)
        return state, y

    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          dt.transpose(1, 0, 2).astype(jnp.float32),
          bh.transpose(1, 0, 2, 3).astype(jnp.float32),
          ch.transpose(1, 0, 2, 3).astype(jnp.float32))
    state0 = jnp.zeros((bsz, h, p_dim, n_dim), jnp.float32)
    _, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype)
