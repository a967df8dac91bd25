"""Mesh construction — the one place the repository builds a mesh.

Functions (not module-level constants) so importing this module never
touches jax device state.  Every mesh is built with ``Auto`` axis
types: ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
``with_sharding_constraint`` refuses the mesh's axes and sharded
gathers demand an ``out_sharding``; the index plane's sharding rules
(``parallel/sharding.py``) and shard_maps are written for ``Auto``.

Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2x16x16 = 512
chips (pod, data, model); the ``pod`` axis composes with ``data`` for
DP/FSDP and optionally carries pipeline stages (parallel/pipeline.py).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape: Sequence[int], axes: Sequence[str],
                   devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh(shape, axes)`` with every axis ``Auto``.
    ``devices`` (optional) are the devices to lay out, e.g. the devices
    of a described topology for an ahead-of-time compile."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for smoke tests on the real CPU."""
    return make_auto_mesh((1, 1), ("data", "model"))


# TPU v5e hardware constants for the roofline (launch/roofline.py)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
