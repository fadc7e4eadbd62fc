"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (required so smoke tests / benches see 1 CPU device while
only the dry-run forces 512 placeholder devices).
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} present; "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before any "
            "jax import (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    n = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def mesh_from_flag(spec: str):
    """Resolve a CLI ``--mesh`` flag to a Mesh (or None).

    'none'   -> no mesh (single device),
    'single' -> production (data=16, model=16) pod,
    'multi'  -> production (pod=2, data=16, model=16),
    'D,M'    -> host mesh (data=D, model=M) over existing devices — the
                multi-device CI shape (XLA_FLAGS=
                --xla_force_host_platform_device_count=N forces N host
                devices before jax import).

    All variants serve both dense and compressed params: BlockCSR /
    PaletteBCSR leaves shard their block store along the block-row slot
    axis and replicate index/gather/palette arrays
    (distributed/sharding.param_shardings).
    """
    if spec in (None, "", "none"):
        return None
    if spec == "single":
        return make_production_mesh()
    if spec == "multi":
        return make_production_mesh(multi_pod=True)
    try:
        data, model = (int(t) for t in spec.split(","))
    except ValueError:
        raise SystemExit(
            f"--mesh must be none|single|multi|DATA,MODEL — got {spec!r}")
    if len(jax.devices()) < data * model:
        raise SystemExit(
            f"--mesh {spec} needs {data * model} devices but only "
            f"{len(jax.devices())} present; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model} "
            "before launch to force host devices")
    return make_host_mesh(data, model)
