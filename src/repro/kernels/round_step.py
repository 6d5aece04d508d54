"""Pallas TPU kernel: the fused OTA round step.

The per-round hot path of the flat aggregation mode used to execute as a
chain of four XLA ops — weighted OTA superposition, noise scaling, noise
injection, SGD parameter update — each making its own pass over the [D]
gradient vector.  This kernel fuses the whole post-gradient round body
into ONE launch:

    ghat[d]  = sum_m qs[m] * g[m, d] * s[m] + noise_scale * z[d]
    out[d]   = params[d] - eta * ghat[d]

g is the [N, D] matrix of raveled per-device precoded gradients, possibly
quantized for the uplink (a real OTA front-end transmits finite-precision
symbols): ``qs`` is the per-device symmetric dequantization scale riding
the round operands (all-ones for f32/bf16 uplinks — the cast alone
dequantizes those).  Everything accumulates in f32 regardless of the wire
dtype; the output is cast to the params dtype on write.

Tile layout (DESIGN.md §Kernels).  The gradient axis is folded into
lane-dense 2-D tiles: D is padded to ``rows * LANES`` and every [D] operand
is viewed as [rows, LANES], the [N, D] uplink as [N, rows, LANES].  A grid
step owns ``block_rows`` rows (a multiple of ``ROW_ALIGN``, the int8
sublane tile, so one layout serves f32, bf16 and int8 wires).  Every
block's last two dims are (block_rows, LANES) — legal on the TPU tiling
whatever leading axes ``jax.vmap`` prepends, which is how the fleet calls
the kernel (one batch axis per grid level: [K] cells, or [K, S]).  The
per-device scalars and (noise_scale, eta) ride one small f32 vector in
SMEM.  The client axis is a loop inside the step, so the only f32
temporaries are [block_rows, LANES] accumulators, never [N, block].

Block size comes from a VMEM budget (``tile_rows``): N rows of the wire
block double-buffered, the z / params / out blocks double-buffered, and
the f32 temporaries must fit ``VMEM_BUDGET``.  D is padded up to a whole
number of blocks, so large cohorts (N=50) get shorter blocks instead of a
compile-time VMEM overflow.

Validated on CPU with interpret=True against ref.ota_round_step_ref.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 512                    # lane width of the 2-D view (4 x 128)
ROW_ALIGN = 32                 # int8 sublane tile; also covers bf16 / f32
VMEM_BUDGET = 8 * 1024 * 1024  # bytes per grid step, buffers + temporaries
_F32_TEMPS = 4                 # acc, one dequantized row, z and params in f32


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def tile_rows(n: int, d: int, wire_bytes: int) -> tuple:
    """(rows, block_rows) of the [rows, LANES] view of a length-``d``
    vector for an ``n``-device uplink of ``wire_bytes`` per element.

    Per element of a block a grid step holds: the n-row wire block twice
    (double-buffered), z / params in and out twice at 4 bytes, and
    ``_F32_TEMPS`` f32 temporaries.  The largest ROW_ALIGN-multiple of rows
    that fits ``VMEM_BUDGET`` bounds the block; the block count is then
    fixed and the rows re-balanced across blocks, so padding stays under one
    ROW_ALIGN stripe per block instead of up to a whole block."""
    per_elem = 2 * n * wire_bytes + 2 * 3 * 4 + _F32_TEMPS * 4
    max_rows = max(ROW_ALIGN,
                   VMEM_BUDGET // (per_elem * LANES) // ROW_ALIGN * ROW_ALIGN)
    need = _cdiv(d, LANES)
    blocks = _cdiv(need, max_rows)
    block_rows = _cdiv(_cdiv(need, blocks), ROW_ALIGN) * ROW_ALIGN
    return blocks * block_rows, block_rows


def _kernel(coef_ref, g_ref, z_ref, p_ref, out_ref):
    # coef_ref: SMEM f32 [1, 2 + 2N] = (noise_scale, eta, s[0:N], qs[0:N])
    # g_ref: [N, BR, LANES] wire dtype; z_ref / p_ref / out_ref: [BR, LANES]
    n = g_ref.shape[0]

    def device(m, acc):
        g = g_ref[m].astype(jnp.float32) * coef_ref[0, 2 + n + m]   # dequant
        return acc + g * coef_ref[0, 2 + m]

    acc = jax.lax.fori_loop(0, n, device,
                            jnp.zeros(out_ref.shape, jnp.float32))
    ghat = acc + coef_ref[0, 0] * z_ref[...].astype(jnp.float32)
    upd = p_ref[...].astype(jnp.float32) - coef_ref[0, 1] * ghat
    out_ref[...] = upd.astype(out_ref.dtype)


def ota_round_step_pallas(g: jax.Array, coef: jax.Array, z: jax.Array,
                          params: jax.Array, *, block_rows: int,
                          interpret: bool = False) -> jax.Array:
    """g: [N, rows, LANES] (any wire dtype incl. int8/bf16); coef: f32
    [1, 2 + 2N] = (noise_scale, eta, s, qs) — 2-D so that its block stays
    the whole trailing array under vmap; z / params: [rows, LANES] with
    rows a multiple of ``block_rows`` (``tile_rows`` picks both).  Returns
    the updated [rows, LANES] params in params.dtype."""
    n, rows, lanes = g.shape
    if rows % block_rows or block_rows % ROW_ALIGN or lanes != LANES:
        raise ValueError(f"tile {g.shape} / block_rows={block_rows} is not "
                         f"a [N, k*block_rows, {LANES}] layout")
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # coef
            pl.BlockSpec((n, block_rows, LANES), lambda i: (0, i, 0)),
            tile,                                                 # z
            tile,                                                 # params
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), params.dtype),
        name="ota_round_step",
        interpret=interpret,
    )(coef, g, z, params)
