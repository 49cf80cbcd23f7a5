"""Production meshes (TPU v5e target).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the ``pod``
axis crosses DCN; ``data`` and ``model`` stay inside a pod's ICI fabric.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; callers (dryrun.py) must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.sharding.rules import MeshAxes

__all__ = [
    "make_production_mesh", "make_test_mesh", "mesh_axes_for",
    "make_client_mesh", "resolve_client_mesh", "shard_clients",
    "make_cluster_mesh", "resolve_cluster_mesh",
]


def _auto_axis_types(n: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axis_types(len(axes)))


def make_test_mesh(data: int = 2, model: int = 2, pod: int | None = None) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (requires enough --xla_force_host devices)."""
    if pod:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                             **_auto_axis_types(3))
    return jax.make_mesh((data, model), ("data", "model"), **_auto_axis_types(2))


def make_client_mesh(num_clients: int, axis_name: str = "data") -> jax.sharding.Mesh:
    """1-D mesh spanning the federated client axis (one client per device).

    This is the layout ``CollectiveBackend`` runs real shard_map collectives
    on: stacked ``(C, ...)`` client trees shard one client per ``axis_name``
    index.  Unlike ``jax.make_mesh`` this takes the first ``num_clients``
    devices, so it works when the host exposes more devices than clients.
    """
    devices = jax.devices()
    if len(devices) < num_clients:
        raise ValueError(
            f"client mesh needs {num_clients} devices, have {len(devices)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count before "
            "jax initializes to emulate more on CPU)"
        )
    return jax.sharding.Mesh(np.asarray(devices[:num_clients]), (axis_name,))


def resolve_client_mesh(spec, num_clients: int, axis_name: str = "data"):
    """Resolve a run-config ``mesh`` field into a Mesh or None.

    ``None`` -> no mesh (vmap emulation).  ``"auto"`` -> a client mesh iff
    the host has at least ``num_clients`` devices, else None.  A Mesh is
    validated (its ``axis_name`` axis must span the client axis one-to-one)
    and passed through.
    """
    if spec is None:
        return None
    if isinstance(spec, jax.sharding.Mesh):
        sizes = dict(zip(spec.axis_names, spec.devices.shape))
        if sizes.get(axis_name) != num_clients:
            raise ValueError(
                f"mesh axis {axis_name!r} has size {sizes.get(axis_name)}, "
                f"need one device per client ({num_clients})"
            )
        return spec
    if spec == "auto":
        if len(jax.devices()) >= num_clients:
            return make_client_mesh(num_clients, axis_name)
        return None
    raise ValueError(f"mesh must be None, 'auto', or a jax Mesh, got {spec!r}")


def shard_clients(tree, mesh: jax.sharding.Mesh, axis_name: str = "data"):
    """Place every stacked ``(C, ...)`` leaf one client per ``axis_name`` index.

    The layout the mesh round step consumes and returns: state placed this
    way before the first step keeps one compiled program and in-place
    donation from the first step on.
    """
    sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis_name))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def make_cluster_mesh(num_clusters: int, axis_name: str = "cluster") -> jax.sharding.Mesh:
    """1-D mesh spanning the cluster-replica axis (one replica per device).

    This is the serving-side twin of :func:`make_client_mesh`: the
    ``ContinuousFederatedServer`` shards its stacked ``(D, ...)`` replica
    tree one cluster per ``axis_name`` index, so training and serving share
    one mesh layout.
    """
    return make_client_mesh(num_clusters, axis_name)


def resolve_cluster_mesh(spec, num_clusters: int, axis_name: str = "cluster"):
    """Resolve a serving ``mesh`` field: None / "auto" / a validated Mesh.

    Same contract as :func:`resolve_client_mesh`, with the axis spanning
    cluster replicas instead of clients.
    """
    return resolve_client_mesh(spec, num_clusters, axis_name)


def mesh_axes_for(mesh: jax.sharding.Mesh) -> MeshAxes:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return MeshAxes(
        model="model",
        data="data",
        pod="pod" if "pod" in sizes else None,
        model_size=sizes["model"],
    )
