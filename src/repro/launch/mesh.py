"""Production mesh construction (assignment §MULTI-POD DRY-RUN).

``make_production_mesh`` is a FUNCTION (never a module constant) so that
importing this module touches no jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and only then calls it.

Single pod  : (16, 16)      axes ("data", "model")   = 256 chips (v5e pod)
Multi pod   : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips;
              the "pod" axis is the DCN/ICI-cross-pod data-parallel axis.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from ..compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(
    model: Optional[int] = None, data: Optional[int] = None
) -> Mesh:
    """Mesh over whatever devices exist (tests, examples, benchmarks)."""
    n = jax.device_count()
    if model is None:
        model = 1
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))


def mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)
