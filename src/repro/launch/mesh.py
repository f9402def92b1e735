"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run on the CPU platform under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(repro.launch.dryrun sets both automatically)")
    import numpy as np
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh() -> Mesh:
    """Degenerate 1x1 mesh over the local device (smoke tests/examples)."""
    import numpy as np
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
