"""Production mesh definitions (TPU v5e pods; CPU placeholders in dry-run).

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_axis: int = 1):
    """A mesh over whatever devices exist (tests / single-host training)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=_auto(2))


def _auto(ndim: int) -> tuple:
    """Auto axis types: the activation rules place specs with
    ``with_sharding_constraint``, which Explicit axes (``jax.make_mesh``'s
    default) refuse."""
    return (jax.sharding.AxisType.Auto,) * ndim


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_LINK_BW = 50e9              # B/s per link (roofline convention: aggregate)
HBM_BYTES = 16 * 1024 ** 3      # 16 GiB
