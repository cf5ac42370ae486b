"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The dry-run process
forces 512 host devices via XLA_FLAGS before any jax import.

Version-gated jax symbols (AxisType, make_mesh kwargs) come from
``repro.compat``.
"""
from __future__ import annotations

from typing import Tuple

from repro import compat
from repro.compat import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes,
                            axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Elastic mesh builder: any (pod,data,model) factorization (used by
    checkpoint-reshard tests and smoke tests)."""
    return compat.make_mesh(shape, axes,
                            axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh():
    return compat.make_mesh((1, 1), ("data", "model"),
                            axis_types=(AxisType.Auto, AxisType.Auto))


MESHES = {
    "single": lambda: make_production_mesh(multi_pod=False),
    "multi": lambda: make_production_mesh(multi_pod=True),
}

HW = {  # TPU v5e-like target constants (per chip)
    "peak_flops_bf16": 197e12,
    "hbm_bw": 819e9,
    "ici_link_bw": 50e9,
    "hbm_bytes": 16 * 1024**3,
}
