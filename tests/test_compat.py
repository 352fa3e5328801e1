"""repro.compat: the manual-collective surface (shard_map, vma helpers)
on the installed JAX (EXPERIMENTS.md §Compat)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from tests.helpers.subproc import run_multidevice


def test_exports_present():
    assert set(compat.__all__) == {"shard_map", "vma_of", "vary",
                                   "axis_size"}
    for name in compat.__all__:
        assert hasattr(compat, name), name
    assert compat.shard_map is jax.shard_map
    assert compat.axis_size is jax.lax.axis_size


def test_pvary_vary_outside_shard_map():
    x = jnp.arange(4.0)
    # an empty axis tuple is an identity, and a value outside shard_map
    # varies over no manual axis
    assert compat.vary(x, ()) is x
    assert compat.vma_of(x) == frozenset()


def test_shard_map_single_device_in_process():
    """The bridge runs in the main test process (1 device, 1-shard mesh)."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    P = jax.sharding.PartitionSpec

    def body(a):
        s = jax.lax.psum(jnp.sum(a), ("x",))
        return compat.vary(jnp.full((2,), s), ("x",)) + compat.axis_size("x")

    f = compat.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    out = np.asarray(f(jnp.arange(2.0)))
    np.testing.assert_allclose(out, [2.0, 2.0])  # sum 1 + axis_size 1


MULTI = """
from jax.sharding import Mesh, PartitionSpec as P
from repro import compat

mesh = Mesh(np.array(jax.devices()), ("x",))
p = 4

def body(a):
    # axis_size is a static int, usable as a shape/constant
    assert compat.axis_size("x") == p
    a = compat.vary(a, ("x",))
    # psum_scatter over equal slices == slice of psum
    full = jax.lax.psum(a, ("x",))
    scat = jax.lax.psum_scatter(a, "x", scatter_dimension=0, tiled=True)
    i = jax.lax.axis_index("x")
    want = jax.lax.dynamic_slice_in_dim(full, i * (a.shape[0] // p),
                                        a.shape[0] // p)
    return jax.lax.pmin(jnp.all(scat == want).astype(jnp.int32), "x")

f = compat.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P())
x = jnp.arange(p * 8, dtype=jnp.float32)
assert int(f(x)) == 1
print("OK")
"""


def test_shard_map_multidevice_semantics():
    out = run_multidevice(MULTI, ndev=4)
    assert "OK" in out
