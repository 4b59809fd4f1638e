"""Production mesh construction (TPU v5e pods).

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
*before* the first jax device query.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(*, data: int = 1, model: int = 1):
    """Small CPU mesh for tests/examples (uses however many host devices exist)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(min(model, n // max(data, 1)), 1)
    return _mesh((data, model), ("data", "model"))


# Hardware constants for the roofline (TPU v5e)
TPU_V5E = {
    "name": "tpu_v5e",
    "peak_flops_bf16": 197e12,  # FLOP/s per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link
    "hbm_bytes": 16e9,  # per chip
}
