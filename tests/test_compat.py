"""Compat-layer smoke tests: every src/repro module imports, and each shim
in repro.compat works under the INSTALLED JAX — future API drift fails
here, in one obvious place, before it breaks a multi-device worker."""
import importlib
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

# Entry points that mutate process-global state at import time (dryrun
# pins XLA_FLAGS for its own 512-device process) — importing them here
# would leak into this process' environment.
SKIP_IMPORT = {"repro.launch.dryrun"}


def _iter_modules():
    for path in sorted((SRC / "repro").rglob("*.py")):
        rel = path.relative_to(SRC).with_suffix("")
        name = ".".join(rel.parts)
        if name.endswith(".__init__"):
            name = name[: -len(".__init__")]
        if name in SKIP_IMPORT:
            continue
        yield name


@pytest.mark.parametrize("name", list(_iter_modules()))
def test_module_imports(name):
    importlib.import_module(name)


def test_make_mesh():
    mesh = compat.make_mesh((1,), ("x",))
    assert mesh.axis_names == ("x",)
    assert mesh.shape["x"] == 1


def test_shard_map_full_manual_and_ppermute():
    mesh = compat.make_mesh((1,), ("x",))
    f = jax.jit(compat.shard_map(
        lambda v: compat.ppermute(v, "x", [(0, 0)]) + 1.0,
        mesh=mesh, in_specs=(P("x"),), out_specs=P("x")))
    out = np.asarray(f(jnp.zeros((1, 4))))
    np.testing.assert_array_equal(out, np.ones((1, 4)))


def test_shard_map_pytree_ppermute():
    mesh = compat.make_mesh((1,), ("x",))

    def body(v):
        tree = {"a": v, "b": (v * 2,)}
        out = compat.ppermute(tree, "x", [(0, 0)])
        return out["a"] + out["b"][0]

    f = jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x")))
    np.testing.assert_array_equal(np.asarray(f(jnp.ones((1, 3)))),
                                  3 * np.ones((1, 3)))


def test_shard_map_partial_manual_axes():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    f = jax.jit(compat.shard_map(
        lambda v: v * 2, mesh=mesh, in_specs=(P("data"),),
        out_specs=P("data"), axis_names={"data"}, check_vma=False))
    np.testing.assert_array_equal(np.asarray(f(jnp.ones((2, 2)))),
                                  2 * np.ones((2, 2)))


def test_axis_size_static():
    mesh = compat.make_mesh((1,), ("x",))

    def body(v):
        p = compat.axis_size("x")
        assert isinstance(p, int), "axis size must be STATIC at trace time"
        return v.reshape(p, -1)[0][None]

    f = jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=(P("x"),), out_specs=P("x")))
    f(jnp.ones((1, 4)))


def test_use_mesh_activates_bare_spec_constraints():
    mesh = compat.make_mesh((1,), ("x",))
    with compat.use_mesh(mesh):
        f = jax.jit(
            lambda v: jax.lax.with_sharding_constraint(v, P("x")))
        np.testing.assert_array_equal(np.asarray(f(jnp.ones((2,)))),
                                      np.ones((2,)))


def test_cost_analysis_normalized_dict():
    c = jax.jit(lambda x: x @ x).lower(jnp.ones((16, 16))).compile()
    ca = compat.cost_analysis(c)
    assert isinstance(ca, dict)
    assert float(ca.get("flops", 0.0)) > 0


def test_no_direct_legacy_call_sites():
    """The compat layer is the ONLY place allowed to touch the moved APIs
    (mirrors the grep acceptance gate of the compat-layer PR)."""
    bad = []
    roots = [SRC, pathlib.Path(__file__).resolve().parent,
             SRC.parent / "benchmarks", SRC.parent / "examples"]
    for root in roots:
        for path in root.rglob("*.py"):
            if path.name == "compat.py" or path == pathlib.Path(__file__):
                continue
            text = path.read_text()
            for needle in ("jax" + ".shard_map", "jax" + ".set_mesh",
                           "jax" + ".make_mesh",  # split: keep THIS file
                           "lax" + ".axis_size"):  # out of the grep gate
                if needle in text:
                    bad.append(f"{path}: {needle}")
    assert not bad, "direct legacy-API call sites outside compat:\n" + \
        "\n".join(bad)


def test_persistent_cache_off_restores_setting():
    was = jax.config.jax_enable_compilation_cache
    with compat.persistent_cache_off():
        assert jax.config.jax_enable_compilation_cache is False
    assert jax.config.jax_enable_compilation_cache == was


def test_make_mesh_over_explicit_device_subset():
    devs = jax.devices()[:1]
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=devs)
    assert list(mesh.devices.flat) == devs
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)
