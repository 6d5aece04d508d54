"""Kernel micro-benchmarks: wall time of the jnp reference path on CPU (the
Pallas kernels themselves target TPU; interpret mode timing is meaningless,
so we time the production jnp paths and report kernel/oracle agreement).

``round_step_rows`` is the fused-vs-unfused round-step section: the whole
flat round tail (dequantize + OTA superposition + noise + SGD step) as ONE
jit'd expression against the historical four-op chain with ``ghat``
materialized between launches, per uplink dtype (f32/bf16/int8) at the
paper's model scale — the walls and bytes-moved numbers that ride
BENCH_engine.json under "round_step" (schema-checked by
benchmarks.validate_bench).  ``python -m benchmarks.kernel_bench --smoke``
additionally runs the interpret-mode Pallas equivalence gate (CI's
benchmark-smoke job; no pytest needed)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.kernels import ops, ref

UPLINKS = ops.UPLINK_DTYPES


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def run() -> list:
    key = jax.random.PRNGKey(0)
    rows = []

    # OTA aggregation at the paper's scale (d = 814,090; N = 10)
    g = jax.random.normal(key, (10, 814_090))
    s = jax.random.uniform(key, (10,))
    z = jax.random.normal(key, (814_090,))
    ns = jnp.float32(0.2)
    t_ref = _time(jax.jit(ref.ota_aggregate_ref), g, s, z, ns)
    out_k = ops.ota_aggregate(g, s, z, ns)
    err = float(jnp.max(jnp.abs(out_k - ref.ota_aggregate_ref(g, s, z, ns))))
    rows.append({"bench": "ota_aggregate_d814k", "us_per_call": round(t_ref, 1),
                 "kernel_max_err": err})

    # SSD scan (model path) vs sequential oracle, S=1024
    b, s_, h, p, gsz, n = 1, 1024, 8, 64, 1, 64
    x = jax.random.normal(key, (b, s_, h, p))
    dt = jax.nn.softplus(jax.random.normal(key, (b, s_, h)))
    a_neg = -jnp.exp(jax.random.normal(key, (h,)) * 0.5)
    bm = jax.random.normal(key, (b, s_, gsz, n)) * 0.3
    cm = jax.random.normal(key, (b, s_, gsz, n)) * 0.3
    from repro.models.ssm import ssd_chunked
    f_chunk = jax.jit(lambda *a: ssd_chunked(*a, chunk=128)[0])
    f_seq = jax.jit(ref.ssd_ref)
    t_chunk = _time(f_chunk, x, dt, a_neg, bm, cm, iters=3)
    t_seq = _time(f_seq, x, dt, a_neg, bm, cm, iters=3)
    err = float(jnp.max(jnp.abs(f_chunk(x, dt, a_neg, bm, cm)
                                - f_seq(x, dt, a_neg, bm, cm))))
    rows.append({"bench": "ssd_chunked_1k", "us_per_call": round(t_chunk, 1),
                 "speedup_vs_sequential": round(t_seq / t_chunk, 2),
                 "max_err": err})
    return rows


def round_step_rows(n: int = 10, d: int = 814_090, iters: int = 5) -> list:
    """Fused vs unfused round-step walls + bytes moved per uplink dtype.

    The fused side is the production CPU expression behind
    ``ops.ota_round_step_pytree`` (one jit'd dequant→aggregate→noise→step);
    the unfused side is the pre-kernel chain — ``ota_aggregate_ref`` as its
    own launch, ``ghat`` materialized, then the separate SGD-update launch
    — which is exactly the extra HBM round-trip the fusion removes.
    Quantize time is excluded from both: it is device-side work that
    happens before the uplink either way.

    ``uplink_mb`` is what the N devices transmit (the over-the-air win of
    a low-precision wire); ``bytes_moved_mb`` is the receiver-side traffic
    of one fused pass (g + z + params in, params out).
    """
    key = jax.random.PRNGKey(0)
    kg, ks, kz, kp = jax.random.split(key, 4)
    g = jax.random.normal(kg, (n, d))
    s = jax.random.uniform(ks, (n,), minval=0.1, maxval=1.0)
    z = jax.random.normal(kz, (d,))
    p = jax.random.normal(kp, (d,))
    ns, eta = jnp.float32(0.2), jnp.float32(0.05)

    fused = jax.jit(lambda w, qs: ref.ota_round_step_ref(
        w, s, z, ns, p, eta, q_scale=qs))

    agg = jax.jit(lambda w, qs: ref.ota_aggregate_ref(
        ops.dequantize_uplink(w, qs), s, z, ns))

    @jax.jit
    def update(ghat):
        return (p - eta * ghat).astype(p.dtype)

    def unfused(w, qs):
        return update(agg(w, qs))

    rows = []
    base = None
    for ud in UPLINKS:
        wire, q_scale = ops.quantize_uplink(g, ud)
        wire = jax.block_until_ready(wire)
        t_f = _time(fused, wire, q_scale, iters=iters)
        t_u = _time(unfused, wire, q_scale, iters=iters)
        out = fused(wire, q_scale)
        if base is None:
            base = out
        err = float(jnp.max(jnp.abs(out - base)))
        uplink_mb = n * d * ops.UPLINK_WIRE_BYTES[ud] / 1e6
        # one fused pass: wire in + z in + params in + params out (f32)
        fused_mb = uplink_mb + 3 * d * 4 / 1e6
        # unfused adds a ghat write + read between the two launches
        unfused_mb = fused_mb + 2 * d * 4 / 1e6
        rows.append({"uplink_dtype": ud,
                     "fused_us": round(t_f, 1),
                     "unfused_us": round(t_u, 1),
                     "speedup": round(t_u / t_f, 2),
                     "uplink_mb": round(uplink_mb, 2),
                     "fused_bytes_mb": round(fused_mb, 2),
                     "unfused_bytes_mb": round(unfused_mb, 2),
                     "max_err_vs_f32": err})
    return rows


def round_step_equivalence(n: int = 4, d: int = 5000) -> float:
    """Interpret-mode Pallas ``ota_round_step`` vs the jnp oracle at a
    non-lane-aligned d, worst uplink error returned (CI smoke gate — the
    same check tests/test_kernels.py runs, without needing pytest)."""
    key = jax.random.PRNGKey(1)
    kg, ks, kz, kp = jax.random.split(key, 4)
    g = jax.random.normal(kg, (n, d))
    s = jax.random.uniform(ks, (n,), minval=0.1, maxval=1.0)
    z = jax.random.normal(kz, (d,))
    p = jax.random.normal(kp, (d,))
    ns, eta = jnp.float32(0.25), jnp.float32(0.05)
    worst = 0.0
    for ud in UPLINKS:
        out = ops.ota_round_step(g, s, z, ns, p, eta, uplink_dtype=ud,
                                 interpret=True)
        wire, q_scale = ops.quantize_uplink(g, ud)
        exp = ref.ota_round_step_ref(wire, s, z, ns, p, eta,
                                     q_scale=q_scale)
        worst = max(worst, float(jnp.max(jnp.abs(out - exp))))
    return worst


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + interpret-mode equivalence gate "
                         "(asserts; CI benchmark-smoke)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.smoke:
        err = round_step_equivalence()
        assert err < 2e-5, f"interpret-mode round_step err {err}"
        print(f"round_step interpret-mode equivalence: max_err={err:.2e} OK")
        rows = round_step_rows(n=4, d=65_536, iters=2)
    else:
        rows = run() + [{"bench": f"ota_round_step_{r['uplink_dtype']}",
                         **r} for r in round_step_rows()]
    for row in rows:
        print(row)
    if args.smoke:
        assert all(r["fused_us"] > 0 and r["unfused_us"] > 0 for r in rows)
        assert {r["uplink_dtype"] for r in rows} == set(UPLINKS)
        print("kernel_bench smoke OK")


if __name__ == "__main__":
    main()
