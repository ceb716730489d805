"""Production meshes.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries extra FL workers (hierarchical over-the-air aggregation crosses
the inter-pod links, which is exactly what the multi-pod dry-run must prove
lowers).
``fsdp > 1`` splits the data plane into ("data", "fsdp") — e.g. fsdp=4 on a
single pod gives 4×4×16 axes ("data", "fsdp", "model"): worker/batch stays
on "data" only, a second parameter dim shards over "fsdp", and the 2D
(fsdp, model) shard grid is the :class:`repro.core.packing.ShardPackSpec`
layout contract.

Every mesh is built by :func:`make_mesh` with ``Auto`` axis types: the
model code annotates activations with ``with_sharding_constraint`` and lets
GSPMD place the rest, which ``Explicit`` axes (the ``jax.make_mesh``
default) refuse.

Defined as functions so importing this module never touches jax device
state; `dryrun.py` sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

#: persistent compile cache of :func:`enable_compile_cache` when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed path at the repo root
#: (the path is part of the cache key, so it must not move between runs)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set
    nothing is configured here; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Entry points call this, never an import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def make_production_mesh(*, multi_pod: bool = False,
                         fsdp: int = 1) -> jax.sharding.Mesh:
    if fsdp <= 1:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return make_mesh(shape, axes)
    if 16 % fsdp:
        raise ValueError(f"fsdp={fsdp} must divide the 16-wide data plane")
    shape = (2, 16 // fsdp, fsdp, 16) if multi_pod \
        else (16 // fsdp, fsdp, 16)
    axes = ("pod", "data", "fsdp", "model") if multi_pod \
        else ("data", "fsdp", "model")
    return make_mesh(shape, axes)


def data_axes(multi_pod: bool) -> Tuple[str, ...]:
    """Mesh axes that jointly carry the batch / FL-worker dimension."""
    return ("pod", "data") if multi_pod else ("data",)


def axis_size(mesh: jax.sharding.Mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n
