"""Device mesh management.

The engine's parallelism vocabulary (reference: SystemPartitioningHandle's
FIXED_HASH_DISTRIBUTION / SOURCE_DISTRIBUTION etc., SURVEY §2d) maps onto a
1-D jax mesh axis "workers": every worker holds a hash slice of each
repartitioned relation; scans shard by row ranges (SOURCE_DISTRIBUTION);
exchanges are XLA collectives over ICI instead of HTTP buffer pulls.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh


WORKERS = "workers"
shard_map = jax.shard_map


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (WORKERS,))
