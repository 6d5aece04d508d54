"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes the
tiling cannot hold, more VMEM than a kernel may use, programs that do not
fit.  These cases compile the fleet's kernel and the x64 SCA design solve
at the paper's width for one v5e chip, so such a fault shows here and not
on the chip.  Nothing runs; a compile that passes says nothing of results
or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.  Keep every such compile in this one file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

D = 814_090                # paper_mlp's parameter count
WIRES = ("f32", "bf16", "int8")      # uplink dtypes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [10, 50])
@pytest.mark.parametrize("wire", list(WIRES))
def test_round_step_kernel_compiles(one_chip, n, wire):
    """The fused round-step kernel at d=814,090 for each uplink dtype, at
    the paper's N=10 and the population stream's 50-device cohort (the
    VMEM-budgeted block must fit)."""
    from repro.kernels import ops

    f32 = jnp.float32
    args = (_sds((n, D), f32, one_chip), _sds((n,), f32, one_chip),
            _sds((D,), f32, one_chip), _sds((), f32, one_chip),
            _sds((D,), f32, one_chip), _sds((), f32, one_chip))
    step = lambda g, s, z, ns, p, eta: ops.ota_round_step(
        g, s, z, ns, p, eta, uplink_dtype=wire, interpret=False)
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("wire", list(WIRES))
def test_round_step_compiles_under_cell_vmap(one_chip, wire):
    """The flat round tail as the fleet calls it: a paper_mlp-shaped
    gradient pytree through ``ota_round_step_pytree``, vmapped over a
    [K=7 scheme, S=2 seed] cell grid.  Every block must stay legal with
    two batch axes in front of it."""
    from repro.kernels import ops
    from repro.models import mlp
    from repro.models.param import init_params

    k, s_axis, n = 7, 2, 10
    params = jax.eval_shape(lambda: init_params(mlp.mlp_defs(),
                                                jax.random.PRNGKey(0)))
    assert sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)) == D
    cells = (k, s_axis)
    f32 = jnp.float32
    grads = jax.tree.map(
        lambda p: _sds(cells + (n,) + p.shape, f32, one_chip), params)
    params_b = jax.tree.map(lambda p: _sds(cells + p.shape, f32, one_chip),
                            params)

    def tail(g, s, ns, key, p, eta):
        return ops.ota_round_step_pytree(g, s, ns, key, p, eta,
                                         uplink_dtype=wire, use_kernel=True,
                                         interpret=False)

    fleet = jax.vmap(jax.vmap(tail))
    compiled = jax.jit(fleet).lower(
        grads, _sds(cells + (n,), f32, one_chip), _sds(cells, f32, one_chip),
        _sds(cells + (2,), jnp.uint32, one_chip), params_b,
        _sds(cells, f32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sca_solve_x64_compiles(one_chip):
    """The x64 SCA design solve (f64 is emulated on the TPU)."""
    from repro.solvers import sca_jax, theory_jax as tj
    from benchmarks.sca_bench import make_prm

    with jax.enable_x64(True):
        pj = tj.from_ota(make_prm(10, 0))
        shapes = jax.tree.map(
            lambda a: _sds(jnp.shape(a), jnp.asarray(a).dtype, one_chip), pj)
        compiled = sca_jax._solve_single_jit.lower(
            shapes, None, sca_jax.DEFAULT_CONFIG, False).compile()
    assert compiled.as_text()
