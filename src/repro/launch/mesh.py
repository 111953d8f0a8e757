"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; callers (dryrun/train)
decide when devices are materialized.
"""

from __future__ import annotations

import jax

def _make_mesh(shape, axes):
    # Auto axes: the sharding is left to the partitioner, as every caller
    # here expects (jax.make_mesh otherwise defaults to Explicit axes).
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(devices_per_axis=(2, 4)):
    """Small mesh for subprocess tests (8 fake devices by default)."""
    axes = ("data", "model") if len(devices_per_axis) == 2 else ("pod", "data", "model")
    return _make_mesh(devices_per_axis, axes)


def make_scenario_mesh(num_devices=None):
    """1-D mesh over the batch (``"data"``) axis for scenario-sharded engines.

    The fused-scan convergence engine shards its ``[S, ...]`` scenario
    batches over this mesh with ``shard_map``.  ``num_devices=None`` uses
    every visible device; otherwise the first ``num_devices`` are taken
    (on CPU, grow the pool with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    avail = len(jax.devices())
    if num_devices is None:
        num_devices = avail
    if not 1 <= num_devices <= avail:
        raise ValueError(
            f"make_scenario_mesh: requested {num_devices} devices but only "
            f"{avail} are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N on CPU)"
        )
    return _make_mesh((num_devices,), ("data",))
