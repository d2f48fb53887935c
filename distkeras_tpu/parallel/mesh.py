"""Mesh construction and placement helpers.

The reference's "cluster" is a Spark app: N executor JVMs plus a driver
(reference ``distkeras/trainers.py:DistributedTrainer``).  Ours is a
``jax.sharding.Mesh``: the ``workers`` axis plays the role of Spark
executors; additional axes (``mp`` for tensor parallelism, ``sp`` for
sequence parallelism) are available to the model layer even though the
reference never had them.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map


def make_mesh(num_workers: Optional[int] = None,
              axis_names: Sequence[str] = ("workers",),
              shape: Optional[Sequence[int]] = None,
              devices=None) -> Mesh:
    """Build a mesh over available devices.

    Default: a 1-D ``("workers",)`` mesh of ``num_workers`` devices — the
    data-parallel topology matching the reference's one-partition-per-worker
    contract.  Pass ``axis_names``/``shape`` for multi-axis (dp × mp × sp)
    meshes.
    """
    if devices is None:
        devices = jax.devices()
    if shape is None:
        n = num_workers if num_workers is not None else len(devices)
        shape = (n,)
    total = int(np.prod(shape))
    if total > len(devices):
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {total} devices, "
            f"have {len(devices)}")
    arr = np.asarray(devices[:total]).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded_on(mesh: Mesh, axis: str = "workers") -> NamedSharding:
    """Leading-dim sharding along ``axis``."""
    return NamedSharding(mesh, P(axis))


def host_to_mesh(mesh: Mesh, tree, axis: str = "workers"):
    """Commit a host pytree with its leading dim sharded over ``axis``.

    One transfer per leaf: the TPU equivalent of Spark shipping each
    partition to its executor.  On a mesh SPANNING ``jax.distributed``
    processes each process contributes only the partitions its own
    devices hold (``spmd.put``) — executor-gets-its-partition for the
    sync dp trainers too (r5)."""
    from .spmd import put
    sh = sharded_on(mesh, axis)
    return jax.tree_util.tree_map(lambda x: put(x, sh), tree)


def broadcast_to_mesh(mesh: Mesh, tree):
    """Commit a host pytree fully replicated (the 'pull' of the center
    variable down to every worker, amortized to one transfer; multi-host
    aware like :func:`host_to_mesh`)."""
    from .spmd import put
    sh = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: put(x, sh), tree)
