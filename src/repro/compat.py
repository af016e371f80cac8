"""Thin helpers over the JAX mesh and Pallas APIs the repo uses.

Every module under ``src/repro/`` reaches these surfaces through the helpers
below (``tests/test_compat.py`` enforces it), so a future API move is one
edit here. The repo runs on one installed JAX; the helpers carry no
fallbacks for older releases.
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax

AxisType = jax.sharding.AxisType


def axis_types_auto(n_axes: int) -> dict:
    """``axis_types=(AxisType.Auto,) * n`` as a splat-able kwargs dict."""
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_mesh(axis_shapes, axis_names, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         **axis_types_auto(len(axis_names)))


def abstract_mesh(axis_shapes, axis_names) -> "jax.sharding.AbstractMesh":
    """An ``AbstractMesh`` from sizes and names, with Auto axis types."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names),
                                     **axis_types_auto(len(axis_names)))


def get_abstract_mesh() -> Optional[Any]:
    """The mesh of the enclosing ``set_mesh`` context, or None.

    Unlike the raw API (which returns an *empty* AbstractMesh when no mesh
    is set), this normalizes to None whenever there is no usable mesh, so
    callers only ever branch on ``mesh is None``.
    """
    m = jax.sharding.get_abstract_mesh()
    if m is None or m.empty or not m.axis_names:
        return None
    return m


@contextlib.contextmanager
def set_mesh(mesh):
    """Enter ``mesh`` as the ambient mesh."""
    with jax.set_mesh(mesh):
        yield mesh


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def cost_analysis(compiled) -> Optional[dict]:
    """``compiled.cost_analysis()`` (a dict, or None where the backend gives
    none)."""
    return compiled.cost_analysis()


def axis_size(axis_name: str):
    """``jax.lax.axis_size`` inside a mapped context."""
    return jax.lax.axis_size(axis_name)


def mesh_axis_sizes(mesh) -> dict[str, int]:
    """{axis name: size} for Mesh and AbstractMesh."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def prefetch_scalar_grid_spec(*, num_scalar_prefetch: int, grid,
                              in_specs, out_specs, scratch_shapes=()):
    """A Pallas grid spec whose first ``num_scalar_prefetch`` operands are
    scalar-prefetch refs (SMEM-resident before the kernel body runs) — the
    delivery channel for the runtime-k noise quantity."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=list(scratch_shapes))
