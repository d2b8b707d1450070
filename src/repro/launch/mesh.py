"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the leading 'pod' axis
carries only data parallelism (gradient all-reduce crosses the DCN/ICI
boundary once per step), never TP.

Defined as functions (not module constants) so importing this module never
touches jax device state — required because the dry-run process must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import math

import numpy as np

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) == n:
        # Auto axes, as ``Mesh`` makes them: the model's sharding
        # constraints name mesh axes that only Auto axes accept
        return jax.make_mesh(shape, axes, axis_types=(
            jax.sharding.AxisType.Auto,) * len(axes))
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax (launch/dryrun.py does this)")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh over however many host devices tests forced."""
    n = math.prod(shape)
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)
