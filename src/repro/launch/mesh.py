"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the 'pod'
axis extends the FL client axis across pods (32 clients) and carries the
cross-pod (DCN-ish) legs of the uplink all-reduce.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; everything else
sees the real device count).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ('data', 'model')
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ('pod', 'data', 'model')


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto-typed axes: the launch shardings, the
    models' sharding constraints and the sharded collectives are GSPMD
    programs, not sharding-in-types ones (jax's default Explicit axes
    reject them)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return make_mesh(shape, axes)


def client_axes(mesh: jax.sharding.Mesh) -> tuple:
    """Mesh axes that enumerate FL clients (every non-'model' axis).
    Delegates to kernels.ops.default_client_axes — the same rule the
    sharded packed collectives use for shard offsets — so the two sides
    cannot disagree about which axes hold clients (import deferred: ops
    pulls the Pallas kernel chain, which mesh construction needn't)."""
    from repro.kernels.ops import default_client_axes
    return default_client_axes(mesh)


def n_clients(mesh: jax.sharding.Mesh) -> int:
    out = 1
    for a in client_axes(mesh):
        out *= mesh.shape[a]
    return out


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist locally, as a 1-D 'data' mesh (CPU tests)."""
    n = len(jax.devices())
    return make_mesh((n,), ('data',))
