"""Logical-axis sharding constraints + the grid shard_map primitive.

Model code annotates activations with *logical* axes (e.g. ("batch", None,
None)); the launcher binds a mesh + rules, and `constrain` lowers to
with_sharding_constraint.  Outside a bound mesh (CPU smoke tests) it is a
no-op, so the same model code serves both paths.

``shard_vmap`` is the embarrassingly-parallel counterpart: it shards a
flattened grid of independent cells (fleet [K x S] cells, SCA scenario
batches) over the mesh with per-device vmap and no collectives — the
substrate of the fleet placement layer (fl.placement, DESIGN.md
§Placement).
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

DEFAULT_RULES = {
    "batch": ("data",),
    "batch_pod": ("pod", "data"),
    "seq": None,
    "kv_seq": None,         # overridden to ("data",) for long-context decode
    "heads": ("model",),
    "ff": ("model",),
    "embed": None,
    "vocab": ("model",),
    "expert": None,
}


def bind(mesh: Mesh, rules: Optional[dict] = None):
    _state.mesh = mesh
    _state.rules = dict(DEFAULT_RULES)
    if rules:
        _state.rules.update(rules)


def unbind():
    _state.mesh = None
    _state.rules = None


@contextlib.contextmanager
def mesh_rules(mesh: Mesh, rules: Optional[dict] = None):
    prev = (getattr(_state, "mesh", None), getattr(_state, "rules", None))
    bind(mesh, rules)
    try:
        yield
    finally:
        _state.mesh, _state.rules = prev


def active_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def logical_to_spec(logical) -> P:
    rules = getattr(_state, "rules", None) or DEFAULT_RULES
    mesh = active_mesh()
    axes = []
    for ax in logical:
        mapped = rules.get(ax) if isinstance(ax, str) else ax
        if mapped is None:
            axes.append(None)
            continue
        if isinstance(mapped, str):
            mapped = (mapped,)
        present = tuple(a for a in mapped if mesh is None
                        or a in mesh.axis_names)
        axes.append(present if present else None)
    return P(*axes)


def constrain(x, logical):
    """Apply a sharding constraint by logical axis names; no-op w/o a mesh."""
    mesh = active_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_logical():
    """'batch' or 'batch_pod' depending on the bound mesh."""
    mesh = active_mesh()
    if mesh is not None and "pod" in mesh.axis_names:
        return "batch_pod"
    return "batch"


def grid_devices(mesh: Mesh, axes=("data", "model")) -> int:
    """Number of devices a flattened grid axis shards over: the product of
    the named mesh axis sizes."""
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return int(n)


def shard_vmap(fn, mesh: Mesh, axes=("data", "model"), num_sharded: int = 1):
    """Map ``fn`` over a leading grid axis, sharded jointly over mesh axes.

    The workhorse of the fleet placement layer (fl.placement, DESIGN.md
    §Placement): ``fn(cell_args..., bcast_args...) -> cell_out`` is a
    per-cell program with NO collectives (cells are independent; the
    shard_map is psum-free).  The returned callable takes the same
    arguments where the first ``num_sharded`` carry a leading grid axis
    [G, ...] on every array leaf and the rest are broadcast (replicated) to
    all devices.  The grid axis is sharded over the *flattened* ``axes`` of
    ``mesh`` — each device vmaps ``fn`` over its local block of cells.

    Padding/masking rule: when G doesn't divide the device count P, the
    grid is right-padded with copies of cell 0 up to the next multiple of P
    (valid inputs, so the padded cells compute real — discarded — work and
    can never poison anything with NaNs), and the padded rows are sliced
    off the outputs.  Outputs come back with the same sharded [G] leading
    axis.
    """
    spec, repl = P(tuple(axes)), P()
    n_dev = grid_devices(mesh, axes)

    def call(*args):
        sharded, bcast = args[:num_sharded], args[num_sharded:]
        g = jax.tree.leaves(sharded[0])[0].shape[0]
        gp = -(-g // n_dev) * n_dev

        def pad(tree):
            return jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.broadcast_to(a[:1], (gp - g,) + a.shape[1:])],
                    axis=0), tree)

        def local(*a):
            s_l, b_l = a[:num_sharded], a[num_sharded:]
            return jax.vmap(fn, in_axes=(0,) * num_sharded
                            + (None,) * len(b_l))(*s_l, *b_l)

        sm = jax.shard_map(
            local, mesh=mesh,
            in_specs=(spec,) * num_sharded + (repl,) * len(bcast),
            out_specs=spec, check_vma=False)
        out = sm(*(sharded if gp == g else tuple(map(pad, sharded))), *bcast)
        if gp != g:
            out = jax.tree.map(lambda a: a[:g], out)
        return out

    return call


# ---------------------------------------------------------------------------
# Multi-process bring-up (DESIGN.md §Grid).
#
# ``jax.distributed.initialize`` wires P processes to one coordinator:
# after it, every process sees the GLOBAL device set and shares the
# coordination service's key-value store.  On the CPU backend, however,
# one XLA computation cannot span processes (XLA raises "Multiprocess
# computations aren't implemented on the CPU backend"), so the bring-up
# rule for grids is PROCESS-SLICED execution: each process runs a
# contiguous slice of the flattened cell axis on a mesh of its LOCAL
# devices, and cross-process agreement is verified by exchanging result
# digests through ``kv_put``/``kv_get`` (benchmarks/grid_smoke.py is the
# 2-process forced-CPU proof).  On accelerator backends the same
# initialize call is the prerequisite for true global-array meshes.
# ---------------------------------------------------------------------------


def initialize_multiprocess(coordinator_address: str, num_processes: int,
                            process_id: int,
                            local_device_count: Optional[int] = None):
    """Join this process to a ``jax.distributed`` cluster.

    Must run before any jax computation touches the backend.
    ``local_device_count`` forces N host-platform (CPU) devices per
    process via XLA_FLAGS — the CI smoke path; leave None on real
    accelerators.  Returns (process_count, local_device_count) as jax
    sees them after initialization.
    """
    if local_device_count:
        flags = os.environ.get("XLA_FLAGS", "")
        forced = f"--xla_force_host_platform_device_count={local_device_count}"
        if forced not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} {forced}".strip()
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return jax.process_count(), jax.local_device_count()


def process_grid_slice(g: int, process_id: Optional[int] = None,
                       num_processes: Optional[int] = None) -> slice:
    """This process's contiguous slice of a flattened grid axis of size
    ``g``: rows [i*ceil(g/P), min((i+1)*ceil(g/P), g)).  Process-major and
    deterministic, so P processes partition the axis exactly; defaults
    come from the initialized jax.distributed runtime."""
    p = jax.process_count() if num_processes is None else int(num_processes)
    i = jax.process_index() if process_id is None else int(process_id)
    if not 0 <= i < p:
        raise ValueError(f"process {i} outside [0, {p})")
    per = -(-g // p)
    return slice(min(i * per, g), min((i + 1) * per, g))


def _coordination_client():
    from jax._src import distributed as _dist  # no public KV API yet
    client = getattr(_dist.global_state, "client", None)
    if client is None:
        raise RuntimeError("jax.distributed is not initialized; call "
                           "initialize_multiprocess first")
    return client


def kv_put(key: str, value: str) -> None:
    """Publish a string under ``key`` in the coordination service's
    key-value store (visible to every process in the cluster)."""
    _coordination_client().key_value_set(key, value)


def kv_get(key: str, timeout_s: float = 60.0) -> str:
    """Block until some process publishes ``key``; returns its value."""
    value = _coordination_client().blocking_key_value_get(
        key, int(timeout_s * 1000))
    return value.decode() if isinstance(value, bytes) else value
