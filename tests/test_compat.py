"""The thin JAX helper layer: every helper must resolve on the installed
JAX, and nothing under src/repro/ bypasses it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat


def test_no_direct_new_api_uses_in_src():
    """Compat policy: nothing under src/repro/ (except compat.py itself)
    touches these JAX surfaces directly — every such call goes through
    repro.compat, so an API move is one edit.  The walk must
    actually reach every package (kernels/, fleet/, analysis/, ... were
    added after this scan was first written; a silent miss would void it)."""
    import os
    root = os.path.join(os.path.dirname(compat.__file__))
    banned = ("jax.sharding.get_abstract_mesh", "jax.sharding.AxisType",
              "jax.lax.axis_size", "jax.sharding.use_mesh", "jax.set_mesh",
              "jax.shard_map", "jax.experimental.shard_map",
              "pltpu.PrefetchScalarGridSpec")
    must_scan = {"core", "hlo", "kernels", "fleet", "launch", "analysis"}
    scanned_pkgs = set()
    hits = []
    for dirpath, _, files in os.walk(root):
        rel = os.path.relpath(dirpath, root)
        if rel != ".":
            scanned_pkgs.add(rel.split(os.sep)[0])
        for fn in files:
            if not fn.endswith(".py") or fn == "compat.py":
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                text = f.read()
            hits += [f"{path}: {b}" for b in banned if b in text]
    missing = must_scan - scanned_pkgs
    assert not missing, f"compat scan never reached packages: {missing}"
    assert not hits, hits


def test_make_mesh_works_on_installed_jax():
    mesh = compat.make_mesh((1,), ("data",))
    assert mesh.axis_names == ("data",)
    assert compat.mesh_axis_sizes(mesh) == {"data": 1}


def test_axis_types_auto_matches_feature_detection():
    kw = compat.axis_types_auto(2)
    assert kw == {"axis_types": (jax.sharding.AxisType.Auto,) * 2}


def test_abstract_mesh_both_signatures():
    m = compat.abstract_mesh((2, 4), ("data", "model"))
    assert m.axis_names == ("data", "model")
    assert compat.mesh_axis_sizes(m) == {"data": 2, "model": 4}


def test_get_abstract_mesh_none_outside_context():
    assert compat.get_abstract_mesh() is None


def test_set_mesh_roundtrip():
    mesh = compat.make_mesh((1,), ("data",))
    with compat.set_mesh(mesh):
        m = compat.get_abstract_mesh()
        assert m is not None
        assert tuple(m.axis_names) == ("data",)
    assert compat.get_abstract_mesh() is None


def test_shard_map_psum():
    mesh = compat.make_mesh((1,), ("data",))
    f = compat.shard_map(lambda x: jax.lax.psum(x, "data"),
                         mesh, in_specs=P(), out_specs=P())
    out = f(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


def test_axis_size_inside_shard_map():
    mesh = compat.make_mesh((1,), ("data",))
    f = compat.shard_map(lambda x: x * compat.axis_size("data"),
                         mesh, in_specs=P(), out_specs=P())
    np.testing.assert_allclose(np.asarray(f(jnp.ones(3))), np.ones(3))


def test_cost_analysis_normalized_to_dict():
    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    cost = compat.cost_analysis(compiled)
    assert isinstance(cost, dict)
    assert cost.get("flops") is not None
