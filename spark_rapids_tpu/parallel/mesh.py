"""Device mesh + ICI collective shuffle layer.

[REF: sql-plugin/../shuffle/ucx/UCX.scala, RapidsShuffleServer/Client] —
re-designed as the SURVEY §2.4 inversion: the reference moves shuffle
blocks point-to-point over UCX (RDMA/NVLink); on TPU the idiomatic
transport is a **collective**: every shuffle stage is one SPMD program
`{hash-partition → all_to_all → local regroup}` over the ICI mesh
(`BASELINE.json` north star).  Multi-chip hardware is not available in
this environment, so the same code paths run on a virtual CPU mesh
(``--xla_force_host_platform_device_count=N``) in tests and are
dry-run-compiled by the driver via ``__graft_entry__.dryrun_multichip``.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.runtime.device import ensure_initialized

SHUFFLE_AXIS = "shuffle"


def make_mesh(n_devices: Optional[int] = None,
              axis: str = SHUFFLE_AXIS) -> jax.sharding.Mesh:
    """1-D mesh over the first n devices (data+shuffle axis).

    SQL shuffle parallelism is 1-D by nature (partitions); wider meshes
    (e.g. per-chip model axes) are not needed for this engine — SURVEY
    §2.3: partition/shuffle parallelism IS the distribution mechanism.
    """
    ensure_initialized()
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.array(devs), (axis,))


def named_sharding(mesh: jax.sharding.Mesh,
                   spec: Optional[jax.sharding.PartitionSpec] = None
                   ) -> jax.sharding.NamedSharding:
    """Row-sharded ``NamedSharding`` over the mesh's shuffle axis — the
    one placement every exchange array (batch leaves, index tables,
    receive-count rows) uses.  ``spec`` overrides for replicated
    operands (``PartitionSpec()``)."""
    if spec is None:
        spec = jax.sharding.PartitionSpec(mesh.axis_names[0])
    return jax.sharding.NamedSharding(mesh, spec)


def all_to_all_shuffle(mesh: jax.sharding.Mesh, parts: jax.Array
                       ) -> jax.Array:
    """The ICI shuffle exchange.

    ``parts``: per-device partitioned rows, shape [D, P, ...] sharded on
    axis 0 (D = mesh size = P): parts[d, p] is the slice device d holds
    destined for device p.  Returns [D, P, ...] where out[d, p] is the
    slice device d received FROM device p — one ``lax.all_to_all`` riding
    ICI, the UCX-fetch analog.
    """
    axis = mesh.axis_names[0]

    def body(x):  # x: [1, P, ...] local block
        y = jax.lax.all_to_all(x[0], axis, split_axis=0, concat_axis=0,
                               tiled=False)
        return y[None]  # [1, P, ...]: row p = slice received from device p

    spec = jax.sharding.PartitionSpec(axis)
    return jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)(parts)
