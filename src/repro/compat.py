"""The single import point for the JAX APIs whose surface has moved between
releases.  The repository targets one JAX line (pinned in
``requirements.txt``; see README §Supported JAX version).  Repro code
NEVER calls `jax.shard_map` / `jax.set_mesh` / `jax.make_mesh` /
`lax.axis_size` directly; it calls the shims below, so that when an API
moves again this module is the only file that changes
(tests/test_compat.py smoke-checks every shim under the installed JAX).

Shims:

  shard_map(...)       ``jax.shard_map`` (``axis_names=`` = the MANUAL axes,
                       ``check_vma=``).
  make_mesh(...)       a mesh with every axis ``Auto`` (``jax.make_mesh``, or
                       an explicit device subset as given): bare-spec
                       ``with_sharding_constraint`` calls and partial-manual
                       ``shard_map`` regions are written against Auto axes
                       (JAX's default is ``Explicit``, under which a
                       bare-spec constraint becomes an assertion).
  use_mesh(mesh)       ``jax.set_mesh`` context (resolves bare
                       PartitionSpecs against ``mesh``).
  cost_analysis(c)     ``Compiled.cost_analysis()`` as a flat dict ({} when
                       the backend gives none).
  axis_size(name)      static size of a mapped axis at trace time.
  ppermute(x, ...)     pytree-aware ``lax.ppermute`` (single call point for
                       the circulant collectives' per-round sends).
  tpu_topology(name)   a described TPU topology (e.g. ``"v5e:2x2"``): its
                       ``devices`` let the TPU compiler compile for a chip
                       that is not attached.
  persistent_cache_off()  context with JAX's persistent compilation cache
                       disabled (a compile for a described chip cannot be
                       read back from it).
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax import lax
from jax.sharding import AxisType, Mesh


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              axis_names: set | frozenset | None = None,
              check_vma: bool | None = None) -> Callable:
    """``jax.shard_map``; ``axis_names`` is the set of MANUAL mesh axes
    (None = all axes manual, the common full-manual case)."""
    kw: dict[str, Any] = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """A mesh with ``Auto`` axis types on every axis: ``jax.make_mesh``
    over the runtime's devices, or ``devices`` laid out in the order
    given (``jax.make_mesh`` insists on a whole physical TPU mesh, and an
    explicit subset -- p < device count, an elastic run's survivors -- is
    not one)."""
    axis_shapes = tuple(axis_shapes)
    axis_names = tuple(axis_names)
    types = (AxisType.Auto,) * len(axis_names)
    if devices is None:
        return jax.make_mesh(axis_shapes, axis_names, axis_types=types)
    return Mesh(np.asarray(list(devices)).reshape(axis_shapes), axis_names,
                axis_types=types)


@contextlib.contextmanager
def use_mesh(mesh):
    """Activate ``mesh`` for the enclosed region (``jax.set_mesh``)."""
    with jax.set_mesh(mesh):
        yield mesh


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a flat dict ({} when absent)."""
    return dict(compiled.cost_analysis() or {})


def axis_size(axis_name: str) -> int:
    """Static size of a mapped axis at trace time."""
    return lax.axis_size(axis_name)


def ppermute(x, axis_name: str, perm: Sequence[tuple[int, int]]):
    """Pytree-aware ``lax.ppermute`` (safe for compressed payload trees)."""
    return jax.tree.map(
        lambda leaf: lax.ppermute(leaf, axis_name, perm), x)


# ---------------------------------------------------------------------------
# Compiling for a TPU that is not attached
# ---------------------------------------------------------------------------

def tpu_topology(name: str):
    """``jax.experimental.topologies`` description of TPU topology
    ``name``; loads the TPU library, so call it only where one process at
    a time does."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu", topology_name=name)


@contextlib.contextmanager
def persistent_cache_off():
    """Disable JAX's persistent compilation cache for the enclosed
    region, restoring the previous setting after."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
