"""Mesh construction with the axis semantics this repo is written for.

``jax.make_mesh`` gives new meshes Explicit axis types; every sharding
rule here (``parallel/sharding.py``, the data-parallel plans, the MoE
dispatch) assumes Auto axes, where the compiler propagates shardings.
"""

from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
