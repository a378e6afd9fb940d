"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries the HAP-ring / data-parallel replication across pods
(DESIGN.md §3).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before the first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding code here
    (``with_sharding_constraint``, ``shard_map``) is written for
    compiler-propagated shardings, which JAX's ``Explicit`` default axes
    refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices the host actually has (tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return make_mesh((data, model), ("data", "model"))


def make_data_mesh():
    """1-D "data" mesh over every local device (trailing size-1 "model"
    axis so the shared rules resolve) — the layout the fused epoch program
    (``core/epoch_step.py``) shards the participant axis over.  On a
    single-device host this is the identity mesh: every shape and result
    stays bit-identical to the unsharded path."""
    return make_host_mesh(data=len(jax.devices()), model=1)


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW_PER_LINK = 50e9          # B/s per link
ICI_LINKS = 4                   # 2D torus on v5e: 4 links/chip
