"""Production meshes.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
is the slow (DCN/inter-pod ICI) dimension; batch shards over ("pod","data").

Functions, not module constants: importing this module must never touch
jax device state (smoke tests and benches run on 1 real CPU device; only
dryrun.py forces the 512-device platform).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: shardings are propagated by the
    compiler from the inputs' and constraints' placements, which is what
    every caller here is written for (``jax.make_mesh`` itself defaults
    to Explicit axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh for in-test lowering on host platforms with few fake
    devices."""
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


# fleet-serving mesh axis: camera groups shard over it (zero cross-group
# leakage by construction makes this axis embarrassingly parallel — the
# sharded super-launch has NO collectives on its hot path)
FLEET_AXIS = "shard"


def make_fleet_mesh(n_shards: int = 0):
    """1-D mesh over the ``"shard"`` axis for the sharded fleet runtime.

    ``n_shards`` = 0 uses every visible device.  On CPU hosts simulate
    multiple devices by exporting
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax
    initializes (the tests/benches do this via subprocesses)."""
    avail = len(jax.devices())
    n = n_shards or avail
    if n > avail:
        raise ValueError(
            f"make_fleet_mesh({n_shards}): only {avail} device(s) visible; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"before jax initializes to simulate more on CPU")
    return make_mesh((n,), (FLEET_AXIS,))


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
CHIPS_PER_POD = 256
HBM_PER_CHIP = 16 * 2 ** 30     # 16 GiB
