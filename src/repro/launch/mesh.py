"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") —
the leading "pod" axis crosses the DCN; batch shards over it, params
replicate across it (FSDP stays intra-pod), gradient all-reduce crosses
it (optionally int8-compressed, see repro.optim.compress).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (smoke tests see 1 CPU device; only dryrun.py
forces 512 host devices via XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple:
    """Auto axes: the sharding rules rely on implicit (GSPMD) propagation."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (dryrun.py does this)."
        )
    return jax.make_mesh(shape, axes, _auto(len(axes)), devices=devices)


def make_local_mesh(model_parallel: int = 1, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel), axes, _auto(len(axes)))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch (pod+data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
