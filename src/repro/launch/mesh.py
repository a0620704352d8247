"""Production mesh builders.

Single pod: 16×16 = 256 chips ("data", "model").
Multi-pod: 2×16×16 = 512 chips ("pod", "data", "model") — the "pod" axis is
additional data parallelism across ICI-disjoint pods (DCN-connected), the
elastic scale-out axis of the paper's horizontal scaling.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    # Auto axes: GSPMD propagates the shardings the policy constrains, as
    # the models are written for (explicit axes would demand an
    # out_sharding on every sharded gather)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, devices=None):
    """Small mesh over however many (possibly fake) local devices exist, or
    over ``devices`` (e.g. a described topology's, for compile-only runs)."""
    return _mesh((data, model), ("data", "model"), devices)


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link (~per chip, ring)
HBM_PER_CHIP = 16e9               # bytes
