"""The sharded entry (``"entry": "sharded"``): ``core/distributed.py:
build_dist_graph`` at the cell's pinned directed slots per shard, then
``core/distributed_sharded.py: distributed_sharded_msf`` on a mesh of
the cell's chips, with the traffic's ``algorithm`` and the engine's
default options, then the slot mask reduced to the edges by ``eid``,
as ``core/mst.py: _distributed_dispatch`` does with an exact capacity
of its own.  The interface is ``bench/entries/static.py``'s;
the overflow returned is the engine's count of dropped exchange items.
"""
from __future__ import annotations


def make(u, v, w, n: int, slots: int, params: dict):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed import build_dist_graph
    from repro.core.distributed_sharded import distributed_sharded_msf

    chips = params["chips"]
    algorithm = params["algorithm"]
    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    m = len(u)

    def solve(clock):
        with clock.span("host_prep"):
            g, _ = build_dist_graph(u, v, w, n, chips, cap=slots)
        with clock.span("engine"):
            res = jax.block_until_ready(distributed_sharded_msf(
                g, n, mesh, algorithm=algorithm))
            slot_mask = np.asarray(res[0])
            overflow = int(res[4])
            out = np.zeros(m, bool)
            out[np.unique(np.asarray(g.eid)[slot_mask])] = True
        return out, overflow

    return solve
