"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the leading "pod" axis
carries data parallelism (or pipeline stages, see distributed/pipeline.py)
across the DCN boundary.

Defined as functions so importing this module never touches jax device
state (required for the dry-run's forced host-device count to win).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
