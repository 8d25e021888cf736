"""Shims over moving JAX APIs and the process-level JAX setup.

One shared helper per API so call sites never track JAX's defaults
themselves:

- ``shard_map``: ``jax.shard_map`` with its ``check_vma`` flag;
- ``make_mesh``: ``jax.make_mesh`` with every axis ``AxisType.Auto``
  (JAX 0.9 defaults to ``Explicit`` axes, under which the sharded
  ``jnp.linalg`` leaves raise ``ShardingTypeError``);
- ``enable_compile_cache``: the persistent compilation cache, placed once by
  an entry point (never at import time or in tests).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

# <repo>/.jax_cache: a fixed path, since the path is part of the cache key
_REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` (``check_vma``: varying-manual-axes checking)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], *, devices: Optional[Sequence] = None
):
    """``jax.make_mesh`` with ``Auto`` axis types on every axis."""
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for an entry point.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    Returns the directory in use.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)
