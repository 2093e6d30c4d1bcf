"""Device-mesh construction for the sharded embedding engine.

The reference's deployment geometry — ``numPartitions`` Spark workers x
``numParameterServers`` Glint servers (README.md:45-57, mllib:354-362) — maps
onto a 2-D TPU mesh:

  axis "data"  (size = num_partitions analogue): batch rows are sharded here;
               each slice processes its share of every minibatch.
  axis "model" (size = numParameterServers analogue): the vocab rows of both
               embedding tables are sharded here; each slice owns
               1/num_shards of syn0 and syn1 (README.md:69).

Collectives ride ICI: a psum over "model" replaces the client<->server
pull RPCs; an all_gather over "data" replaces the async push of gradient
scalars (SURVEY.md §2.3 comm-backend row).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    num_data: Optional[int] = None,
    num_model: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ("data", "model") mesh over the available devices.

    Defaults: all devices on the model axis (pure vocab sharding — the
    topology closest to the reference's PS cluster) unless sizes are given.
    When both sizes are given, the first ``num_data * num_model`` devices
    are used (so a small mesh can run on a larger host, mirroring the
    reference's freedom to run fewer parameter servers than executors).
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if num_data is None and num_model is None:
        num_data, num_model = 1, n
    elif num_data is None:
        if n % num_model:
            raise ValueError(f"{n} devices not divisible by num_model={num_model}")
        num_data = n // num_model
    elif num_model is None:
        if n % num_data:
            raise ValueError(f"{n} devices not divisible by num_data={num_data}")
        num_model = n // num_data
    if num_data * num_model > n:
        raise ValueError(
            f"mesh {num_data}x{num_model} needs more than the {n} available devices"
        )
    grid = np.asarray(devs[: num_data * num_model]).reshape(num_data, num_model)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Vocab-row sharding for syn0/syn1: rows split over "model", dim
    replicated — each model slice is one 'parameter server'."""
    return NamedSharding(mesh, P(MODEL_AXIS, None))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Minibatch rows split over "data", replicated over "model"."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of m that is >= n."""
    return ((n + m - 1) // m) * m
