"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The single-pod mesh is
(data=16, model=16) = 256 chips of a v5e pod; multi-pod adds a leading
pod axis: (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over however many (fake) host devices exist — for tests."""
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    return _make_mesh(shape, axes)


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~ per chip hop)
HBM_PER_CHIP = 16 * 1024**3     # 16 GiB
