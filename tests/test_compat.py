"""Sharding-API helper tests (``repro.common.compat``)."""
import jax
import numpy as np

from repro.common import compat
from repro.sharding.specs import auto_mesh

P = jax.sharding.PartitionSpec


def test_shard_map_executes_on_installed_jax():
    """shard_map runs a real collective program under a set mesh."""
    mesh = auto_mesh((1,), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    fn = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=P("x"),
                                  out_specs=P(), check_vma=True))
    with jax.set_mesh(mesh):
        out = fn(np.arange(4.0, dtype=np.float32))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


def test_axis_size_and_pcast_inside_shard_map():
    """pcast_varying accepts values already varying over the axis (a
    sharded input) and values that are not (a replicated input)."""
    mesh = auto_mesh((1,), ("x",))

    def f(a, b):
        s = jax.lax.axis_size("x")
        bv = compat.pcast_varying(b, ("x",))
        assert "x" in jax.typeof(bv).vma
        return compat.pcast_varying(a, ("x",)) * s + bv

    fn = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=(P("x"), P()),
                                  out_specs=P("x")))
    out = fn(np.ones(2, np.float32), np.ones(2, np.float32))
    np.testing.assert_allclose(np.asarray(out), 2 * np.ones(2))


def test_auto_mesh_axes_are_auto():
    mesh = auto_mesh((1, 1), ("data", "model"))
    assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types)


def test_default_interpret_matches_backend():
    assert compat.default_interpret() == (jax.default_backend() != "tpu")
