"""The JAX set-up this repo shares: mesh and shard_map helpers, and the
persistent compilation cache.

The mesh helpers spell Auto axis types once, so every mesh in the repo is
built the same way; the repo targets the JAX pinned in requirements.txt.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

#: the checkout root (src/repro/jaxcompat.py → three levels up)
CHECKOUT = Path(__file__).resolve().parents[2]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names), axis_types=(AxisType.Auto,) * len(axis_names)
    )


def device_mesh(devices, axis_names: Sequence[str]) -> Mesh:
    """``Mesh`` over an explicit device array, Auto axis types."""
    return Mesh(devices, tuple(axis_names), axis_types=(AxisType.Auto,) * len(axis_names))


def make_abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]) -> AbstractMesh:
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
    path, because the path is part of what a later process must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
