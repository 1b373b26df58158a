"""Production mesh builders (assignment-specified topology).

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import; tests and benches see the real single device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 single pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 4, model: int = 2) -> Mesh:
    """Small mesh for unit tests (requires ≥ data·model fake devices)."""
    return _make_mesh((data, model), ("data", "model"))


def _make_1d_mesh(axis: str, n_devices=None) -> Mesh:
    """1-D mesh over the first ``n_devices`` visible devices (all by
    default).  Unlike ``jax.make_mesh`` this accepts a device count
    below the total, so a 2-way run works on an 8-fake-device test
    process."""
    import numpy as np

    avail = jax.devices()
    n = len(avail) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(avail):
        raise ValueError(
            f"{axis} mesh needs 1 <= n_devices <= {len(avail)} visible "
            f"devices, got {n} (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N for fake devices)")
    return Mesh(np.asarray(avail[:n]), (axis,),
                axis_types=(AxisType.Auto,))


def make_data_mesh(n_devices=None) -> Mesh:
    """1-D ``("data",)`` mesh — the data-parallel streaming topology
    (``train.data_parallel``): batches shard over the axis, parameters
    replicate, gradients all-reduce with ``psum_mean``."""
    return _make_1d_mesh("data", n_devices)


def make_replica_mesh(n_replicas=None) -> Mesh:
    """1-D ``("replica",)`` mesh — the serving replica topology
    (``serving.engine.HashedClassifierEngine(replicas=N)``): the model
    is device_put ONCE per replica and bucket lanes round-robin their
    micro-batches across the axis; no collectives, throughput scales
    with independent devices."""
    return _make_1d_mesh("replica", n_replicas)
