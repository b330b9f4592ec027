"""Production mesh definitions.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets the virtual device count before
any jax initialization).
"""
from __future__ import annotations

import jax

from repro.core.topology import make_mesh


def make_production_mesh(*, multi_pod: bool = False, tp: int = 16):
    """16x16 chips per pod; the multi-pod mesh adds a 2-pod DCN axis.

    `tp` retiles the same 256 chips/pod between the data and model axes
    (TP degree is a per-architecture tunable: small models want tp<=2,
    MoE wants tp ~ expert granularity)."""
    per_pod = 256
    assert per_pod % tp == 0
    shape = (2, per_pod // tp, tp) if multi_pod else (per_pod // tp, tp)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(devices: int | None = None, tp: int | None = None):
    """A (1, dp, tp) smoke/serving mesh over the first `devices` devices
    (default: every device JAX sees)."""
    devices = devices or jax.device_count()
    tp = tp or (2 if devices % 2 == 0 else 1)
    return make_mesh((1, devices // tp, tp), ("pod", "data", "model"))
