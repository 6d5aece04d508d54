"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # hypothesis is optional: the deterministic equivalence sweeps must
    # run everywhere (they are the kernel correctness gate); only the
    # property tests skip without it
    def _skip_prop(*args, **kwargs):
        def deco(fn):
            return pytest.mark.skip(reason="needs hypothesis")(fn)
        return deco

    given = settings = _skip_prop

    class st:  # noqa: N801 — placeholder so strategies parse at import
        def __getattr__(self, _):
            return lambda *a, **k: None
        integers = floats = sampled_from = booleans = lists = \
            staticmethod(lambda *a, **k: None)

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    # bf16: the kernel accumulates in fp32, the oracle in bf16 — the kernel
    # is the more accurate side, so tolerance covers oracle rounding
    return dict(rtol=6e-2, atol=6e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ota_aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 10, 32])
@pytest.mark.parametrize("d", [128, 1024, 5000, 65536])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ota_aggregate_sweep(n, d, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    g = jax.random.normal(k1, (n, d), dtype)
    s = jax.random.uniform(k2, (n,), jnp.float32)
    z = jax.random.normal(k3, (d,), jnp.float32)
    out = ops.ota_aggregate(g, s, z, jnp.float32(0.25))
    exp = ref.ota_aggregate_ref(g, s, z, jnp.float32(0.25))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 16), st.integers(1, 2000), st.integers(0, 2**31 - 1))
def test_ota_aggregate_property(n, d, seed):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    g = jax.random.normal(k1, (n, d))
    s = jax.random.uniform(k2, (n,))
    z = jax.random.normal(k3, (d,))
    out = ops.ota_aggregate(g, s, z, jnp.float32(0.0))
    exp = ref.ota_aggregate_ref(g, s, z, jnp.float32(0.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ota_round_step (fused round tail: dequant + aggregate + noise + SGD step)
# ---------------------------------------------------------------------------

_UPLINK_OF = {jnp.float32: "f32", jnp.bfloat16: "bf16", jnp.int8: "int8"}


def _round_operands(n, d, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    g = jax.random.normal(k1, (n, d), jnp.float32)
    s = jax.random.uniform(k2, (n,), jnp.float32)
    z = jax.random.normal(k3, (d,), jnp.float32)
    p = jax.random.normal(k4, (d,), jnp.float32)
    return g, s, z, p


@pytest.mark.parametrize("n", [1, 10])
# 5000: non-aligned; 100_003: several grid steps at N=10
@pytest.mark.parametrize("d", [128, 1024, 5000, 100_003])
@pytest.mark.parametrize("wire", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_ota_round_step_kernel_vs_ref(n, d, wire):
    """Interpret-mode Pallas kernel vs the flat jnp oracle, including the
    padding edge (d=5000 and 100_003 do not fill whole [rows, LANES] blocks:
    padded g/z/params elements must never leak into the first d outputs)."""
    g, s, z, p = _round_operands(n, d)
    ns, eta = jnp.float32(0.25), jnp.float32(0.05)
    out = ops.ota_round_step(g, s, z, ns, p, eta,
                             uplink_dtype=_UPLINK_OF[wire], interpret=True)
    w, q_scale = ops.quantize_uplink(g, _UPLINK_OF[wire])
    exp = ref.ota_round_step_ref(w, s, z, ns, p, eta, q_scale=q_scale)
    assert out.shape == (d,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [1, 10, 50, 200])
@pytest.mark.parametrize("wire_bytes", [4, 2, 1])
def test_tile_rows_fit_the_vmem_budget(n, wire_bytes):
    """The block layout at the paper's width: whole 32-row blocks that
    cover d with under one 32-row stripe of padding per block, and a grid
    step's buffers and f32 temporaries within the VMEM budget."""
    from repro.kernels import round_step as rs

    d = 814_090
    rows, block_rows = rs.tile_rows(n, d, wire_bytes)
    blocks = rows // block_rows
    assert rows % block_rows == 0 and block_rows % rs.ROW_ALIGN == 0
    assert rows * rs.LANES >= d
    assert rows - (-(-d // rs.LANES)) < blocks * rs.ROW_ALIGN
    per_elem = 2 * n * wire_bytes + 2 * 3 * 4 + 4 * 4
    assert (block_rows == rs.ROW_ALIGN
            or block_rows * rs.LANES * per_elem <= rs.VMEM_BUDGET)


def _tree_oracle(grads, params, s, ns, k_noise, eta):
    # the historical per-leaf round tail: tree-map weighted sum, per-leaf
    # keyed receiver noise, per-leaf SGD update
    from repro.core import ota
    agg = ota.weighted_sum(grads, s)
    ghat = ota.add_receiver_noise(agg, ns, k_noise)
    return jax.tree.map(
        lambda p, g: (p.astype(jnp.float32)
                      - eta * g.astype(jnp.float32)).astype(p.dtype),
        params, ghat)


@pytest.mark.parametrize("shapes", [
    {"w": (17, 9), "b": (23,)},                 # non-aligned leaf sizes
    {"w": (64, 128), "b": (128,), "o": (3,)},
])
def test_ota_round_step_pytree_vs_tree_oracle(shapes):
    n = 6
    kg, kp, ks, kn = jax.random.split(KEY, 4)
    grads = {k: jax.random.normal(jax.random.fold_in(kg, i), (n,) + s)
             for i, (k, s) in enumerate(shapes.items())}
    params = {k: jax.random.normal(jax.random.fold_in(kp, i), s)
              for i, (k, s) in enumerate(shapes.items())}
    s = jax.random.uniform(ks, (n,), jnp.float32)
    ns, eta = jnp.float32(0.3), jnp.float32(0.05)
    exp = _tree_oracle(grads, params, s, ns, kn, eta)
    for kwargs in ({}, {"use_kernel": True, "interpret": True}):
        got = ops.ota_round_step_pytree(grads, s, ns, kn, params, eta,
                                        **kwargs)
        for k in shapes:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(exp[k]),
                                       rtol=2e-5, atol=2e-5)


def test_ota_round_step_pytree_mixed_leaf_dtypes():
    """bf16 + f32 leaves: the fused path accumulates in the widest dtype
    and casts per leaf on unflatten; the kernel must agree with the CPU
    oracle, and both must track the tree oracle to bf16 tolerance."""
    n = 4
    kg, kp, ks, kn = jax.random.split(KEY, 4)
    grads = {"w": jax.random.normal(kg, (n, 40, 3), jnp.bfloat16),
             "b": jax.random.normal(jax.random.fold_in(kg, 1), (n, 50))}
    params = {"w": jax.random.normal(kp, (40, 3), jnp.bfloat16),
              "b": jax.random.normal(jax.random.fold_in(kp, 1), (50,))}
    s = jax.random.uniform(ks, (n,), jnp.float32)
    ns, eta = jnp.float32(0.3), jnp.float32(0.05)
    cpu = ops.ota_round_step_pytree(grads, s, ns, kn, params, eta)
    kern = ops.ota_round_step_pytree(grads, s, ns, kn, params, eta,
                                     use_kernel=True, interpret=True)
    exp = _tree_oracle(grads, params, s, ns, kn, eta)
    for k in grads:
        assert cpu[k].dtype == params[k].dtype
        np.testing.assert_allclose(np.asarray(kern[k], np.float32),
                                   np.asarray(cpu[k], np.float32),
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(cpu[k], np.float32),
                                   np.asarray(exp[k], np.float32),
                                   **_tol(params[k].dtype))


def test_ota_round_step_f32_bitwise_with_unfused_flat():
    """uplink_dtype="f32" fused == the pre-kernel flat path (aggregate
    via ota_aggregate_pytree, then the tree-map SGD update) — bitwise."""
    n = 10
    kg, kp, ks, kn = jax.random.split(KEY, 4)
    shapes = {"w": (31, 7), "b": (13,)}
    grads = {k: jax.random.normal(jax.random.fold_in(kg, i), (n,) + s)
             for i, (k, s) in enumerate(shapes.items())}
    params = {k: jax.random.normal(jax.random.fold_in(kp, i), s)
              for i, (k, s) in enumerate(shapes.items())}
    s = jax.random.uniform(ks, (n,), jnp.float32)
    ns, eta = jnp.float32(0.3), jnp.float32(0.05)
    ghat = ops.ota_aggregate_pytree(grads, s, ns, kn)
    old = jax.tree.map(
        lambda p, g: (p.astype(jnp.float32)
                      - eta * g.astype(jnp.float32)).astype(p.dtype),
        params, ghat)
    new = ops.ota_round_step_pytree(grads, s, ns, kn, params, eta)
    for k in shapes:
        assert np.array_equal(np.asarray(old[k]), np.asarray(new[k]))


def test_uplink_quantized_fused_matches_unfused():
    """bf16/int8: the fused step and the unfused quantized aggregation +
    update see the same wire values and the same f32 math — identical."""
    n = 5
    kg, kp, ks, kn = jax.random.split(KEY, 4)
    grads = {"w": jax.random.normal(kg, (n, 41, 5))}
    params = {"w": jax.random.normal(kp, (41, 5))}
    s = jax.random.uniform(ks, (n,), jnp.float32)
    ns, eta = jnp.float32(0.3), jnp.float32(0.05)
    for ud in ("bf16", "int8"):
        ghat = ops.ota_aggregate_pytree(grads, s, ns, kn, uplink_dtype=ud)
        unf = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - eta * g.astype(jnp.float32)).astype(p.dtype),
            params, ghat)
        fus = ops.ota_round_step_pytree(grads, s, ns, kn, params, eta,
                                        uplink_dtype=ud)
        np.testing.assert_array_equal(np.asarray(unf["w"]),
                                      np.asarray(fus["w"]))


def test_uplink_dtype_validation():
    g = jnp.ones((2, 8))
    with pytest.raises(ValueError):
        ops.quantize_uplink(g, "f16")
    from repro.core import ota
    with pytest.raises(ValueError):
        ota.apply_round_coeffs({"w": jnp.ones((2, 4))}, jnp.ones(2),
                               0.1, KEY, flat=False, uplink_dtype="int8")


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 8), st.integers(1, 300), st.integers(0, 2**31 - 1),
       st.floats(1e-6, 1e4))
def test_int8_uplink_roundtrip_property(n, d, seed, scale_mag):
    """Quantize→dequantize error is bounded by half a quantization step
    per element (per-device symmetric scale = amax/127), at every
    magnitude: the scale must adapt per device, not globally."""
    k = jax.random.PRNGKey(seed)
    mags = jnp.logspace(-1, 1, n).reshape(n, 1) * scale_mag
    g = jax.random.normal(k, (n, d)) * mags
    wire, q_scale = ops.quantize_uplink(g, "int8")
    assert wire.dtype == jnp.int8
    back = ops.dequantize_uplink(wire, q_scale)
    step = np.asarray(q_scale)[:, None]
    err = np.abs(np.asarray(back) - np.asarray(g, np.float32))
    assert np.all(err <= 0.5 * step * (1 + 1e-5) + 1e-30)
    # and the wire really is symmetric: codes stay in [-127, 127]
    assert np.abs(np.asarray(wire)).max() <= 127


def test_run_fleet_f32_fused_bitwise_parity():
    """End-to-end acceptance pin: through ``driver.run_fleet`` the fused
    default (flat=True) is bitwise the pre-kernel unfused flat path
    (fuse_round=False) — params AND every per-round trace."""
    from repro.core import power_control as pcm, scenarios as scn
    from repro.data import partition, synthetic
    from repro.fl import driver
    from repro.fl.server import FLRunConfig
    from repro.models import mlp
    from repro.models.param import init_params

    dep = scn.realize(scn.get_scenario("disk_markov"))
    prm = scn.make_ota_params(dep, d=10000, gmax=10.0, eta=0.05,
                              kappa_sq=4.0)
    x, y, _, _ = synthetic.mnist_like(40, seed=0)
    data = partition.stack_shards(partition.partition_by_label(
        x, y, 10, seed=0))
    params0 = init_params(mlp.mlp_defs(hidden=16), jax.random.PRNGKey(0))
    schemes = [pcm.make_power_control(nm, dep, prm)
               for nm in ("vanilla", "ideal")]
    run = FLRunConfig(eta=0.05, num_rounds=4, eval_every=2, batch_size=8)
    args = (mlp.mlp_loss, params0, schemes, dep.gains, data, run)
    fused = driver.run_fleet(*args, flat=True, seeds=(0,))
    unfused = driver.run_fleet(*args, flat=True, seeds=(0,),
                               fuse_round=False)
    for a, b in zip(jax.tree.leaves(fused.params),
                    jax.tree.leaves(unfused.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert set(fused.traces) == set(unfused.traces)
    for k in fused.traces:
        assert np.array_equal(np.asarray(fused.traces[k]),
                              np.asarray(unfused.traces[k])), k


# ---------------------------------------------------------------------------
# models/ssm.py chunked SSD vs the sequential oracle
# ---------------------------------------------------------------------------

def test_ssd_model_path_matches_ref():
    """models/ssm.ssd_chunked (the production path) == sequential oracle."""
    from repro.models.ssm import ssd_chunked
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    b, s, h, p, g, n = 2, 64, 4, 16, 2, 8
    x = jax.random.normal(k1, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k2, (b, s, h)))
    a_neg = -jnp.exp(jax.random.normal(k3, (h,)) * 0.5)
    bm = jax.random.normal(k4, (b, s, g, n)) * 0.5
    cm = jax.random.normal(k1, (b, s, g, n)) * 0.5
    y, _ = ssd_chunked(x, dt, a_neg, bm, cm, chunk=16)
    exp = ref.ssd_ref(x, dt, a_neg, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_ssd_state_carry_consistency():
    """Splitting the sequence and carrying state == processing it whole."""
    from repro.models.ssm import ssd_chunked
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    b, s, h, p, g, n = 1, 64, 2, 8, 1, 8
    x = jax.random.normal(k1, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k2, (b, s, h)))
    a_neg = -jnp.exp(jax.random.normal(k3, (h,)) * 0.5)
    bm = jax.random.normal(k4, (b, s, g, n)) * 0.5
    cm = jax.random.normal(k1, (b, s, g, n)) * 0.5
    y_full, st_full = ssd_chunked(x, dt, a_neg, bm, cm, chunk=16)
    half = s // 2
    y1, st1 = ssd_chunked(x[:, :half], dt[:, :half], a_neg, bm[:, :half],
                          cm[:, :half], chunk=16)
    y2, st2 = ssd_chunked(x[:, half:], dt[:, half:], a_neg, bm[:, half:],
                          cm[:, half:], chunk=16, state0=st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               rtol=1e-4, atol=1e-4)
