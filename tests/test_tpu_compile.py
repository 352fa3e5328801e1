"""Rehearsal compiles for one described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: these
tests compile the main path's programs for a described ``v5e:2x2``
topology, so what Mosaic or XLA would refuse on the chip fails here.
Nothing runs, so they say nothing about results or times.  The topology
is described inside a module fixture (only one process may load the TPU
library) and every test skips when it cannot be described.  The
persistent compilation cache stays off around them: such entries could
not be read back without a chip.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def test_owner_scatter_min_compiles(one_chip):
    """The fused MINEDGES kernel at the engine's shapes lowers through
    Mosaic (a 1-D tiling aborts the compiler process)."""
    from repro.kernels.segmin.segmin import owner_scatter_min
    L, size = 32768, 4096

    def spec(dt):
        return jax.ShapeDtypeStruct((L,), dt, sharding=one_chip)

    def f(i, w, e, a, b, ok):
        return owner_scatter_min(i, w, e, a, b, ok, size, interpret=False)

    compiled = jax.jit(f).lower(
        spec(jnp.int32), spec(jnp.float32), spec(jnp.int32),
        spec(jnp.int32), spec(jnp.int32), spec(jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_owner_scatter_min_compiles_in_shard_map(topo):
    """The same kernel as the engine calls it: inside ``shard_map`` on a
    one-chip mesh, so its inputs carry a varying manual axis and its
    ``out_shape`` must declare it."""
    from repro import compat
    from repro.kernels.segmin.segmin import owner_scatter_min
    L, size = 32768, 4096
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    sh = NamedSharding(mesh, P("data"))

    def body(i, w, e, a, b, ok):
        assert compat.vma_of(i) == {"data"}
        return owner_scatter_min(i, w, e, a, b, ok, size, interpret=False)

    f = jax.jit(compat.shard_map(body, mesh=mesh,
                                 in_specs=(P("data"),) * 6,
                                 out_specs=(P("data"),) * 4))

    def spec(dt):
        return jax.ShapeDtypeStruct((L,), dt, sharding=sh)

    compiled = f.lower(
        spec(jnp.int32), spec(jnp.float32), spec(jnp.int32),
        spec(jnp.int32), spec(jnp.int32), spec(jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_boruvka_msf_compiles(one_chip):
    from repro.core.boruvka import boruvka_msf
    n, m = 1 << 16, 1 << 18
    iv = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    wv = jax.ShapeDtypeStruct((m,), jnp.float32, sharding=one_chip)
    compiled = boruvka_msf.lower(iv, iv, wv, n).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9


def test_round_counter_keeps_memory_placement(one_chip, monkeypatch):
    """The static engine's live-slot count leaves XLA's memory-space
    assignment as it is without the count: at the Kronecker cell's size
    every gather and scatter result keeps its memory space (a count that
    kept its own [m] mask pushed one 2^24-slot gather's result out of
    memory space ``S(1)``, and that gather ran a fifth slower on a v5e)."""
    from repro.core import boruvka
    n, m = 1 << 20, 1 << 24
    iv = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    wv = jax.ShapeDtypeStruct((m,), jnp.float32, sharding=one_chip)

    def custom_fusions():
        fn = jax.jit(lambda u, v, w: boruvka.boruvka_msf_counted.__wrapped__(
            u, v, w, n))
        text = fn.lower(iv, iv, wv).compile().as_text()
        return re.findall(r"= (\S+) fusion\(.*?kind=kCustom", text)

    counted = custom_fusions()
    min_edges = boruvka._min_edges
    monkeypatch.setattr(boruvka, "_min_edges", lambda ru, rv, w, n: (
        *min_edges(ru, rv, w, n)[:2], jnp.int32(0)))
    assert custom_fusions() == counted
    assert sum("S(1)" in t for t in counted) > 4


def test_sharded_round_step_compiles(topo):
    """The fused sharded engine's step on a one-chip mesh."""
    from repro.core.distributed_sharded import make_sharded_mst_step
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    sh = NamedSharding(mesh, P("data"))
    step, specs = make_sharded_mst_step(256, 2048, mesh,
                                        shrink_capacities=False)
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
             for s in specs]
    compiled = jax.jit(step).lower(*specs).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
