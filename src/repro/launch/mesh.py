"""Production meshes + per-replica submesh carving.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import so 512 placeholder devices exist; smoke tests and benchmarks see the
single real CPU device.

``carve_submeshes`` is the serving fleet's device partitioner: N disjoint
``(data, model)`` submeshes, one per router replica, all driven by the
thread-per-replica fleet loop in one process.  The multi-host variant
(one OS process per replica joined via ``jax.distributed.initialize``)
shares the interface but is stubbed — see
:func:`distributed_replica_mesh`.
"""
from __future__ import annotations

from typing import List

import jax
import numpy as np
from jax.sharding import AxisType

from repro.config import MULTI_POD, SINGLE_POD, MeshConfig


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    jax's default is *Explicit* axes, under which a sharding rule that
    names one axis twice (e.g. FSDP ``data`` on a weight plus ``data``
    on the batch) raises instead of letting the compiler insert the
    collectives; every mesh of this repo is built here instead.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The 256-chip single-pod (or 512-chip two-pod) production mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    """The :class:`MeshConfig` matching :func:`make_production_mesh`."""
    return MULTI_POD if multi_pod else SINGLE_POD


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many host devices exist (tests)."""
    return make_mesh(shape, axes)


def carve_submeshes(num_replicas: int, shape=(1, 2),
                    axes=("data", "model"), devices=None) -> List:
    """Carve the process's devices into per-replica serving submeshes.

    Returns ``num_replicas`` disjoint ``jax.sharding.Mesh`` objects of
    ``shape`` over ``axes``, slicing ``devices`` (default
    ``jax.devices()``) in order — replica r owns devices
    ``[r*k, (r+1)*k)`` with ``k = prod(shape)``.  Disjointness is what
    lets the thread-per-replica fleet loop drive them concurrently:
    replicas share no device, so their collectives never interleave.
    Raises ``ValueError`` when the host has too few devices.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    k = int(np.prod(shape))
    need = num_replicas * k
    if len(devices) < need:
        raise ValueError(
            f"carve_submeshes: need {need} devices ({num_replicas} "
            f"replicas x {shape}), have {len(devices)}.  Force host "
            "devices with XLA_FLAGS=--xla_force_host_platform_device_"
            "count=N or lower --replicas/--mesh-shape.")
    return [
        jax.sharding.Mesh(
            np.asarray(devices[r * k:(r + 1) * k]).reshape(shape), axes)
        for r in range(num_replicas)
    ]


def distributed_replica_mesh(replica_index: int, num_replicas: int,
                             shape=(1, 2), axes=("data", "model"),
                             coordinator: str = "localhost:1234"):
    """Process-per-replica fleet over ``jax.distributed`` (stub).

    The multi-host deployment runs one OS process per replica: each
    process calls ``jax.distributed.initialize(coordinator,
    num_processes=num_replicas, process_id=replica_index)``, builds its
    replica's mesh from ``jax.local_devices()`` with exactly the layout
    :func:`carve_submeshes` uses in-process, and fronts it with the same
    ``ReplicaRouter`` — the rendezvous hash tier keeps fleet resizes at
    ~1/(N+1) moved preamble groups either way, so scale-out economics
    are identical.  The engine/scheduler/router code is already
    process-agnostic (replicas share no state but the router ledger,
    which becomes an RPC service here); what's missing is only the
    cross-process response/submit transport, so this entry point raises
    until that lands.
    """
    raise NotImplementedError(
        "process-per-replica serving over jax.distributed is documented "
        "but not wired yet: run the thread-per-replica fleet over "
        "carve_submeshes() instead (launch.serve --mesh-shape/--tp).")
