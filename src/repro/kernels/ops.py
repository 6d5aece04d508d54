"""Jit'd public wrappers for the Pallas kernels.

On CPU the kernel executes with interpret=True — the kernel body runs as
traced JAX ops, validating indexing/masking/accumulation logic; on TPU the
same pallas_call lowers to Mosaic.  Wrappers pad and tile the operands to
the kernel's lane-dense layout.

Everything that moves the gradient stack into and out of that layout —
ravel, per-leaf noise draw, pad, tiling, quantization, unravel — runs
under the ``fl.uplink`` name scope (``UPLINK_SCOPE``), so a profile tells
it from the kernel itself, which the round body calls under ``fl.step``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import round_step as rs

# bytes per element each uplink puts on the wire (an f32 uplink sends the
# gradient dtype as is, so a bf16 gradient stays 2 bytes)
UPLINK_WIRE_BYTES = {"f32": 4, "bf16": 2, "int8": 1}
UPLINK_DTYPES = tuple(UPLINK_WIRE_BYTES)

# int8 symmetric quantization: values map to [-127, 127] (the -128 code is
# unused so the grid is symmetric around zero — standard for weights/grads)
INT8_LEVELS = 127.0

# name scope of the flat path's layout work (metadata only)
UPLINK_SCOPE = "fl.uplink"


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, size: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to ``size``."""
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, widths)


def quantize_uplink(g: jax.Array, uplink_dtype: str):
    """Device-side uplink quantization of the [N, ...] precoded gradients
    (the raveled [N, D] stack, or its [N, rows, LANES] kernel tiling).

    Returns ``(wire, q_scale)`` — the array as transmitted plus the
    per-device symmetric dequantization scale (None when the wire dtype
    dequantizes by cast alone):

      f32   passthrough — ``wire is g`` exactly, so the f32 uplink cannot
            move a bit anywhere downstream.
      bf16  round-to-nearest-even cast; dequant is the f32 upcast.
      int8  per-device symmetric scale over the device's full raveled
            gradient (every axis after the first; zero padding never moves
            the max): scale_m = max_d |g[m, d]| / 127, wire = round(g /
            scale) clipped to [-127, 127].  Quantization error per element
            is bounded by scale_m / 2.

    The scale rides the round operands next to ``s`` — it is data the
    receiver needs per round, not a compile-time constant.
    """
    if uplink_dtype == "f32":
        return g, None
    if uplink_dtype == "bf16":
        return g.astype(jnp.bfloat16), None
    if uplink_dtype == "int8":
        amax = jnp.max(jnp.abs(g.astype(jnp.float32)),
                       axis=tuple(range(1, g.ndim)))
        scale = jnp.maximum(amax, jnp.finfo(jnp.float32).tiny) / INT8_LEVELS
        q = jnp.round(g.astype(jnp.float32)
                      / scale.reshape((-1,) + (1,) * (g.ndim - 1)))
        return jnp.clip(q, -INT8_LEVELS, INT8_LEVELS).astype(jnp.int8), scale
    raise ValueError(f"uplink_dtype must be one of {UPLINK_DTYPES}, "
                     f"got {uplink_dtype!r}")


def dequantize_uplink(wire: jax.Array, q_scale) -> jax.Array:
    """Receiver-side inverse of ``quantize_uplink`` (always f32 out)."""
    gf = wire.astype(jnp.float32)
    if q_scale is None:
        return gf
    return gf * q_scale[:, None].astype(jnp.float32)


def ota_aggregate(g: jax.Array, s: jax.Array, z: jax.Array,
                  noise_scale: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Fused OTA aggregation over [N, D] gradients: sum_m s_m g_m +
    noise_scale * z, cast to g.dtype.

    Runs the round-step kernel with params = 0 and eta = -1, whose output
    ``0 - (-1) * ghat`` is ghat exactly — one kernel serves both the fused
    round tail and the aggregate-only reference chain."""
    d = g.shape[1]
    out = ota_round_step(g, s, z, noise_scale, jnp.zeros((d,), jnp.float32),
                         jnp.float32(-1.0), interpret=interpret)
    return out.astype(g.dtype)


def _tiled(x: jax.Array, rows: int) -> jax.Array:
    """Zero-pad the last axis of ``x`` to rows * LANES and view it as the
    kernel's [rows, LANES] tiling."""
    return jnp.reshape(_pad_to(x, x.ndim - 1, rows * rs.LANES),
                       x.shape[:-1] + (rows, rs.LANES))


@functools.partial(jax.jit, static_argnames=("uplink_dtype", "interpret"))
def ota_round_step(g: jax.Array, s: jax.Array, z: jax.Array,
                   noise_scale: jax.Array, params: jax.Array,
                   eta: jax.Array, *, uplink_dtype: str = "f32",
                   interpret: Optional[bool] = None) -> jax.Array:
    """Fused OTA round step over [N, D] precoded gradients + [D] params
    (see round_step.py): quantize for the uplink, dequantize, weighted-
    superpose, noise-inject and SGD-update, the last four in one Pallas
    launch.

    Pads D to the kernel's [rows, LANES] tiling (``rs.tile_rows`` sizes
    the blocks from the kernel's VMEM budget) and quantizes the gradient
    stack already tiled (``quantize_uplink``), so a narrow wire is written
    once, in place; re-tiling an int8 or bf16 [N, D] stack would be one
    more copy, and one that XLA's TPU compiler takes minutes to compile
    under the fleet's vmap.  Zero padding moves no int8 scale."""
    interpret = _on_cpu() if interpret is None else interpret
    n, d = g.shape
    wire_bytes = min(jnp.dtype(g.dtype).itemsize,
                     UPLINK_WIRE_BYTES.get(uplink_dtype, 4))
    rows, block_rows = rs.tile_rows(n, d, wire_bytes)
    with jax.named_scope(UPLINK_SCOPE):
        wire, q_scale = quantize_uplink(_tiled(g, rows), uplink_dtype)
        qs = jnp.ones((n,), jnp.float32) if q_scale is None \
            else q_scale.astype(jnp.float32)
        coef = jnp.concatenate(
            [jnp.asarray(noise_scale, jnp.float32).reshape(1),
             jnp.asarray(eta, jnp.float32).reshape(1),
             s.astype(jnp.float32), qs])[None]
        z_t, p_t = _tiled(z, rows), _tiled(params, rows)
    out = rs.ota_round_step_pallas(wire, coef, z_t, p_t,
                                   block_rows=block_rows, interpret=interpret)
    with jax.named_scope(UPLINK_SCOPE):
        return out.reshape(-1)[:d]


def ota_round_step_pytree(stacked, s: jax.Array, noise_scale,
                          key: jax.Array, params, eta, *,
                          uplink_dtype: str = "f32",
                          use_kernel: Optional[bool] = None,
                          interpret: Optional[bool] = None):
    """The whole flat-path round body — quantized uplink, OTA aggregation,
    receiver noise, SGD step — as ONE fused launch over the raveled model.

    ``stacked`` is the gradient pytree with leading client axis [N, ...];
    ``params`` is the matching parameter pytree (no client axis).  Both are
    raveled to single [N, D] / [D] arrays, devices quantize the precoded
    gradient per ``uplink_dtype`` (``quantize_uplink``), and one kernel
    launch dequantizes, f32-accumulates sum_m s_m g_m + noise_scale * z and
    applies ``p - eta * ghat`` — four XLA ops and two extra HBM round-trips
    collapsed into one pass.  Returns the updated parameter pytree, cast
    back to each leaf's dtype.

    Noise keying is byte-identical to ``ota_aggregate_pytree``: split(key,
    n_leaves), leaf l draws normal(keys[l], leaf_size), concatenated — so
    an f32 uplink consumes the same randomness and computes the same
    expression as the unfused flat path and stays bitwise with it (pinned
    in tests/test_kernels.py).

    Dispatch follows ``ota_aggregate_pytree`` exactly: TPU → Pallas kernel;
    CPU → the pure-jnp flattened oracle ``ref.ota_round_step_ref``
    (interpret mode only when ``use_kernel=True`` is forced, as the
    equivalence tests do); both quantize the same values
    (``ota_round_step`` does it in the kernel's tiling).
    """
    from repro.kernels import ref

    g_leaves, _ = jax.tree.flatten(stacked)
    p_leaves, p_def = jax.tree.flatten(params)
    if len(g_leaves) != len(p_leaves):
        raise ValueError("gradient and parameter pytrees do not match")
    sizes = [int(np.prod(l.shape[1:])) for l in g_leaves]
    dtype = jnp.result_type(*[l.dtype for l in g_leaves])
    n = g_leaves[0].shape[0]
    with jax.named_scope(UPLINK_SCOPE):
        g = jnp.concatenate([l.reshape(n, -1).astype(dtype)
                             for l in g_leaves], axis=1)
        keys = jax.random.split(key, len(g_leaves))
        z = jnp.concatenate([jax.random.normal(k, (sz,))
                             for k, sz in zip(keys, sizes)]).astype(dtype)
        p_flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                  for l in p_leaves])
    ns = jnp.asarray(noise_scale, dtype)
    eta32 = jnp.asarray(eta, jnp.float32)
    if use_kernel is None:
        use_kernel = not _on_cpu()
    if use_kernel:
        out = ota_round_step(g, s, z, ns, p_flat, eta32,
                             uplink_dtype=uplink_dtype, interpret=interpret)
    else:
        with jax.named_scope(UPLINK_SCOPE):
            wire, q_scale = quantize_uplink(g, uplink_dtype)
        out = ref.ota_round_step_ref(wire, s, z, ns, p_flat, eta32,
                                     q_scale=q_scale)
    offsets = np.cumsum([0] + sizes)
    with jax.named_scope(UPLINK_SCOPE):
        parts = [out[offsets[i]:offsets[i + 1]].reshape(np.shape(l)).astype(
            l.dtype) for i, l in enumerate(p_leaves)]
    return jax.tree.unflatten(p_def, parts)


def ota_aggregate_pytree(stacked: jax.Array, s: jax.Array, noise_scale,
                         key: jax.Array, *, uplink_dtype: str = "f32",
                         use_kernel: Optional[bool] = None,
                         interpret: Optional[bool] = None):
    """Fused OTA aggregation over a whole gradient *pytree* in one launch.

    ``stacked`` is a pytree whose every leaf has a leading client axis
    [N, ...].  The leaves are raveled once into a single [N, D] matrix and
    the per-round hot path — sum_m s_m g_m + noise_scale * z, f32
    accumulation — runs as ONE flattened reduction instead of a tree of
    per-leaf weighted sums plus per-leaf noise draws.

    Dispatch: on TPU the reduction is the Pallas ``ota_aggregate`` kernel;
    on CPU it is the pure-jnp oracle ``ref.ota_aggregate_ref`` on the same
    flattened arrays — Pallas interpret mode is a correctness emulator,
    orders of magnitude slower at runtime, so it is only entered when
    ``use_kernel=True`` is forced (as the kernel equivalence tests do).

    The receiver noise is a single fused draw, but it is keyed per leaf
    exactly like ``core.ota.add_receiver_noise`` (split(key, n_leaves),
    leaf l reads normal(keys[l], leaf_size)): the flattened path therefore
    consumes the same randomness and produces the same noise *realizations*
    as the tree-map oracle, so the two paths agree to float rounding.

    Leaf shapes need no alignment — the [N, D] matrix is lane-padded by
    ``ota_aggregate`` below.  Mixed leaf dtypes are accumulated in the
    widest input dtype and cast back per leaf on unflatten.

    ``uplink_dtype`` simulates the quantized uplink on the unfused path:
    the raveled gradients round-trip through ``quantize_uplink`` /
    ``dequantize_uplink`` before aggregation (``"f32"`` is a literal
    no-op — same array object, bitwise today's path).  The fused
    ``ota_round_step_pytree`` applies the identical quantization, so the
    fused and unfused paths see the same wire values for every dtype.
    """
    from repro.kernels import ref

    leaves, treedef = jax.tree.flatten(stacked)
    sizes = [int(np.prod(l.shape[1:])) for l in leaves]
    dtype = jnp.result_type(*[l.dtype for l in leaves])
    n = leaves[0].shape[0]
    with jax.named_scope(UPLINK_SCOPE):
        g = jnp.concatenate([l.reshape(n, -1).astype(dtype) for l in leaves],
                            axis=1)
        if uplink_dtype != "f32":
            wire, q_scale = quantize_uplink(g, uplink_dtype)
            g = dequantize_uplink(wire, q_scale).astype(dtype)
        keys = jax.random.split(key, len(leaves))
        z = jnp.concatenate([jax.random.normal(k, (sz,))
                             for k, sz in zip(keys, sizes)]).astype(dtype)
    if use_kernel is None:
        use_kernel = not _on_cpu()
    if use_kernel:
        out = ota_aggregate(g, s, z, noise_scale, interpret=interpret)
    else:
        out = ref.ota_aggregate_ref(g, s, z,
                                    jnp.asarray(noise_scale, dtype))
    offsets = np.cumsum([0] + sizes)
    with jax.named_scope(UPLINK_SCOPE):
        parts = [out[offsets[i]:offsets[i + 1]].reshape(l.shape[1:]).astype(
            l.dtype) for i, l in enumerate(leaves)]
    return jax.tree.unflatten(treedef, parts)
