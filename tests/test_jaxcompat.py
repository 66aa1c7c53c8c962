"""Unit tests for `repro.runtime.jaxcompat` — these run on a single device."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, Mesh, PartitionSpec as P

from repro.runtime import jaxcompat as jc


def test_make_mesh_single_device():
    mesh = jc.make_mesh((1,), ("x",))
    assert isinstance(mesh, Mesh)
    assert dict(mesh.shape) == {"x": 1}


def test_shard_map_runs_on_single_device_mesh():
    mesh = jc.make_mesh((1,), ("x",))
    f = jc.shard_map(lambda a: a * 2, mesh=mesh, in_specs=(P("x"),),
                     out_specs=P("x"))
    np.testing.assert_array_equal(np.asarray(f(jnp.arange(4.0))),
                                  [0.0, 2.0, 4.0, 6.0])


def test_shard_map_check_replication_kwarg():
    """Both values of the portable kwarg map onto the installed jax."""
    mesh = jc.make_mesh((1,), ("x",))
    x = jnp.ones((2,))
    for check in (False, True):
        f = jc.shard_map(lambda a: a + 1, mesh=mesh, in_specs=(P("x"),),
                         out_specs=P("x"), check_replication=check)
        np.testing.assert_array_equal(np.asarray(f(x)), [2.0, 2.0])


def test_abstract_mesh_bridge():
    m = jc.abstract_mesh((2, 4), ("data", "model"))
    assert isinstance(m, AbstractMesh)
    assert dict(m.shape) == {"data": 2, "model": 4}
    assert m.shape["data"] == 2 and m.shape["model"] == 4


def test_abstract_mesh_mismatched_args_raise():
    with pytest.raises(ValueError):
        jc.abstract_mesh((2, 2), ("data",))
