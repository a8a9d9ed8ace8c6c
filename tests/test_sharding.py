"""Sharding rules: logical axes -> PartitionSpec, divisibility fallback,
struct building, layer-axes encoding."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.sharding.context import constrain, mesh_context
from repro.sharding.rules import (
    ParamDef, defs_to_shape_structs, defs_to_shardings, init_from_defs,
    layer_axes_strs, logical_to_pspec)


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


def test_pspec_basic(mesh):
    spec = logical_to_pspec((16, 32), ("embed", "mlp"), mesh)
    assert spec == P("data", "model")


def test_pspec_divisibility_fallback(mesh):
    # dim 3 not divisible by... host mesh is 1x1 so everything divides;
    # build a fake 2-way check via rules on a (2,) mesh axis
    m = make_host_mesh()
    spec = logical_to_pspec((3, 7), ("embed", "mlp"), m)
    assert spec == P("data", "model")   # 1-way always divides


def test_pspec_missing_axis_replicates(mesh):
    spec = logical_to_pspec((8,), ("pod_only_axis",), mesh)
    assert spec == P(None)


def test_defs_to_structs_no_allocation(mesh):
    defs = {"w": ParamDef((1024, 1024), ("embed", "mlp"))}
    structs = defs_to_shape_structs(defs, mesh)
    assert isinstance(structs["w"], jax.ShapeDtypeStruct)
    assert structs["w"].shape == (1024, 1024)
    assert structs["w"].sharding is not None


def test_init_matches_defs():
    defs = {"w": ParamDef((4, 8), ("embed", "mlp")),
            "b": ParamDef((8,), ("mlp",), "zeros")}
    params = init_from_defs(jax.random.PRNGKey(0), defs)
    assert params["w"].shape == (4, 8)
    assert float(jnp.sum(jnp.abs(params["b"]))) == 0.0


def test_layer_axes_strs_drops_layers():
    defs = {"w": ParamDef((12, 4, 8), ("layers", "embed", "mlp")),
            "s": ParamDef((12, 4), ("layers", None))}
    strs = layer_axes_strs(defs)
    assert strs["w"] == "embed|mlp"
    assert strs["s"] == ""


def test_constrain_noop_outside_mesh():
    x = jnp.ones((4, 4))
    assert constrain(x, ("embed", "mlp")) is x


def test_constrain_inside_mesh(mesh):
    x = jnp.ones((4, 4))
    with mesh_context(mesh):
        y = jax.jit(lambda a: constrain(a, ("embed", "mlp")))(x)
    assert y.shape == (4, 4)


def test_shardings_tree_structure(mesh):
    defs = {"a": ParamDef((4,), ("mlp",)),
            "nested": {"b": ParamDef((2, 2), (None, None))}}
    sh = defs_to_shardings(defs, mesh)
    assert set(sh) == {"a", "nested"}
