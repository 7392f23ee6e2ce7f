"""Mesh construction.

Functions, not module-level constants, so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips
(TPU v5e pod).  Multi-pod: (pod=2, data=16, model=16) = 512 chips; the
"pod" axis is pure data parallelism across the DCI.  ``shard_mesh`` is the
1-d mesh of the sharded index's front doors.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / elastic rescale experiments."""
    return jax.make_mesh(tuple(shape), tuple(axes))


def shard_mesh(n: int, axis: str = "shard"):
    """A 1-d ``(axis,)`` mesh over the first ``n`` of ``jax.devices()``.

    The CPU client is first given ``n`` host devices, so that where JAX
    runs on the CPU (no accelerator, or ``JAX_PLATFORMS=cpu``) they stand in
    for chips.  The setting belongs to the CPU client alone: on an
    accelerator the mesh holds the real devices.  It takes effect only
    before the backends start, so call this before any other JAX work;
    afterwards the devices already there are used."""
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backends already started
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"{n} shards need {n} devices, found {len(devices)} "
            f"{devices[0].platform} device(s); on the CPU, build the mesh "
            f"before any other JAX work"
        )
    return jax.make_mesh((n,), (axis,), devices=devices[:n])
