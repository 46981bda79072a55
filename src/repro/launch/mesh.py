"""Production mesh construction (DESIGN.md §5).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, while smoke tests and benches see the single real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def _auto_mesh(shape, axes) -> Mesh:
    """A mesh whose axes GSPMD partitions (the layout the sharding rules
    and ZeRO specs are written for); ``jax.make_mesh`` would otherwise
    make them explicit axes that sharding-in-types checks op by op."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh() -> Mesh:
    """(n, 1) data × model mesh over the real local device(s)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def num_chips(mesh: Mesh) -> int:
    return mesh.devices.size
