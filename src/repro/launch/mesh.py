"""Production meshes. Functions only — importing this module never touches
jax device state (DESIGN.md §6 / dry-run contract)."""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips).

    Axes: ("data", "model") or ("pod", "data", "model"). The paper's M
    federated clients are the ("pod", "data") ranks; "model" is 16-way
    tensor parallelism inside each client.
    """
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run under "
            "launch/dryrun.py (it forces 512 host devices) or on real hardware"
        )
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_attached_mesh(pods: int = 1):
    """Mesh over every attached device, one federated client per device:
    ("data", "model") = (n, 1), or ("pod", "data", "model") =
    (pods, n // pods, 1) for the two-level wire. On one chip that is m = 1,
    on a four-chip host m = 4."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    n = len(devices)
    if pods == 1:
        return Mesh(np.asarray(devices).reshape(n, 1), ("data", "model"))
    if n % pods:
        raise ValueError(f"{n} devices do not split into {pods} pods")
    return Mesh(np.asarray(devices).reshape(pods, n // pods, 1),
                ("pod", "data", "model"))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests on forced host devices."""
    import jax
    from jax.sharding import Mesh

    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)


def client_axes(mesh) -> tuple[str, ...]:
    """The mesh axes that enumerate federated clients (everything but TP)."""
    return tuple(n for n in mesh.axis_names if n != "model")


def num_clients(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in client_axes(mesh)]))


def pod_axes(mesh) -> tuple[str, ...]:
    """The outer (inter-pod) wire axes — present only on multi-pod meshes."""
    return ("pod",) if "pod" in mesh.axis_names else ()


def data_axes(mesh) -> tuple[str, ...]:
    """The inner (intra-pod) client axes: everything but TP and "pod"."""
    return tuple(n for n in mesh.axis_names if n not in ("model", "pod"))


def num_pods(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in pod_axes(mesh)])) if pod_axes(
        mesh) else 1
