"""Production mesh builders (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices before first init.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types. A bare make_mesh defaults its axes
    to Explicit sharding, under which vmap refuses inputs whose mapped axis
    is sharded differently (the FL engine's client-sharded carry next to
    replicated per-round keys)."""
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh(shape, axes, axis_types=(auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ("data","model") single pod; (2,16,16) ("pod","data","model")
    for the 2-pod = 512-chip deployment."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_batch_mesh(axis: str = "batch"):
    """1-D serving mesh over all local devices: the batch axis of each
    inference bucket shards across it (``ForecastServer(shard_batch=True)``
    pairs this with ``repro.core.fl.engine.axis0_shardings``)."""
    return _make_mesh((len(jax.devices()),), (axis,))


def make_client_mesh(axis: str = "clients", *, multi_host: bool = False):
    """1-D FL client mesh: the axis ``run_fl(shard_clients=True)`` and
    ``engine.client_state_shardings`` put the (K, D) client-state rows on.

    Default (``multi_host=False``): THIS process's local devices only — the
    single-host sharding path, identical to the mesh the engine builds
    internally. ``multi_host=True``: every device of the ``jax.distributed``
    cluster in process order (``launch.distributed.initialize_distributed``
    must have run first), so each process holds only its own row block of
    the client state and ``run_fl(driver="while"|"scan")`` spans hosts."""
    import numpy as np
    from jax.sharding import Mesh

    devices = (sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
               if multi_host else list(jax.local_devices()))
    return Mesh(np.asarray(devices), (axis,))


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests/examples on CPU)."""
    n = len(jax.devices())
    model = min(model, n)
    return _make_mesh((n // model, model), ("data", "model"))
