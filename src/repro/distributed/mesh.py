"""Client-axis mesh factory.

The FL client axis is embarrassingly parallel — TiFL-style tiers are
independent workers, and the wireless analyses assume per-device
compute — so the distributed engine shards cohorts over a 1-D
``("clients",)`` mesh.  This composes with the production factories in
``launch/mesh.py``: pass ``devices=mesh.devices.flatten()`` to carve
the client axis out of devices an existing mesh owns, or nothing to
span every visible device (on CPU CI that is whatever
``--xla_force_host_platform_device_count`` forced).

A function, not a module-level constant: importing this module never
touches jax device state (the same convention as ``launch/mesh.py``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np

CLIENT_AXIS = "clients"


def make_client_mesh(clients: Optional[int] = None, *,
                     devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """1-D ``("clients",)`` mesh over the first ``clients`` devices.

    ``clients=None`` spans every available device; a request for more
    devices than exist raises, so ``--mesh-clients 4`` on a one-chip
    machine cannot quietly run the single-device engine.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs) if clients is None else int(clients)
    if n < 1:
        raise ValueError(f"client mesh needs at least one device, got {n}")
    if n > len(devs):
        raise ValueError(f"client mesh of {n} devices requested, but only "
                         f"{len(devs)} exist")
    return jax.sharding.Mesh(np.asarray(devs[:n]), (CLIENT_AXIS,))
