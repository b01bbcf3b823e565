"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; callers (dryrun.py)
are responsible for setting ``--xla_force_host_platform_device_count`` BEFORE
the first jax call.

Axis vocabulary (DESIGN.md §4):

* ``client`` — the federated-population axis.  The packed ``(K, D)`` proposal
  buffer, the per-client data stacks, and the reputation posteriors are all
  sharded over it; AFA's screening runs hierarchically across it (shard-local
  stats + O(K)-scalar collectives).  Dedicated axis, never reused for batch
  parallelism.
* ``data`` / ``pod`` — batch/data parallelism inside one client's SGD step
  (the distributed train-step path).
* ``model`` — tensor parallelism over feature dimensions.
"""

from __future__ import annotations

import jax
import numpy as np

CLIENT_AXIS = "client"


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_client_mesh(num_shards: int):
    """1-D ``(client,)`` mesh over the first ``num_shards`` devices — the
    mesh the sharded fused engine (fed/engine.py) runs under."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    devices = jax.devices()
    if num_shards > len(devices):
        raise ValueError(
            f"client mesh wants {num_shards} devices but only "
            f"{len(devices)} are available"
        )
    return jax.sharding.Mesh(
        np.array(devices[:num_shards]),
        (CLIENT_AXIS,),
        axis_types=(jax.sharding.AxisType.Auto,),
    )


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0, client: int = 0):
    """Small mesh for CPU integration tests (run under
    XLA_FLAGS=--xla_force_host_platform_device_count=<n> in a subprocess).

    ``client`` > 0 prepends a dedicated client axis (the fused-engine
    sharding tests use ``client=N, data=0``-style pure client meshes via
    ``make_client_mesh``; mixed meshes are for the distributed train-step)."""
    shape, axes = (), ()
    if client:
        shape, axes = shape + (client,), axes + (CLIENT_AXIS,)
    if pod:
        shape, axes = shape + (pod,), axes + ("pod",)
    if data:
        shape, axes = shape + (data,), axes + ("data",)
    if model:
        shape, axes = shape + (model,), axes + ("model",)
    if not axes:
        raise ValueError("make_test_mesh needs at least one non-zero axis")
    return _make_mesh(shape, axes)


def client_axis(mesh) -> str | None:
    """The mesh's client axis name, or None when it has no client axis.
    Callers should use this instead of string-matching ``mesh.axis_names``."""
    return CLIENT_AXIS if CLIENT_AXIS in mesh.axis_names else None


def data_axes(mesh) -> tuple:
    """The batch-parallel axes of a mesh: ('pod','data') when present."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def client_row_axes(mesh) -> tuple:
    """Mesh axes a leading CLIENT dimension shards over: the dedicated
    client axis when the mesh has one, else the data axes (the legacy
    clients-on-data-rows mapping, kept for client-free meshes)."""
    ca = client_axis(mesh)
    return (ca,) if ca is not None else data_axes(mesh)


def num_client_rows(mesh) -> int:
    """Number of client rows the mesh spreads a leading client dimension
    over: the client axis size when the mesh has one, else the product of
    the data-like axis sizes (the legacy clients-on-data-rows mapping)."""
    ca = client_axis(mesh)
    if ca is not None:
        return int(mesh.shape[ca])
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
