"""The sharded engine's communication levers against the oracle.

Each lever of ``distributed_sharded_msf`` toggled alone, plus all
together and through the fused Pallas MINEDGES kernel, must keep the
MSF edge set bit-identical to the Kruskal oracle on the adversarial
families of tests/helpers/graph_families.py (8 virtual devices, one
subprocess per family).  Kept apart from test_engine_equivalence.py so
that the two long files run on different test workers.
"""
import inspect

import pytest

from tests.helpers import graph_families
from tests.helpers.subproc import run_multidevice


# the sharded engine's communication levers, each toggled alone
# plus all together, must keep the MSF edge set bit-identical to the
# oracle on the adversarial families (heavy ties exercise the (w, eid)
# tie-break through the src-only owner-side marking; disconnected
# exercises the dead-edge retirement's termination)
SHARDED_FLAGS = inspect.getsource(graph_families) + """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.graph import from_numpy
from repro.core.mst import minimum_spanning_forest

mesh = Mesh(np.array(jax.devices()), ("data",))
OFF = dict(local_preprocessing=False, coalesce=False, src_only=False,
           adaptive_doubling=False, shrink_capacities=False,
           ghost_cache=False, relabel_skip=False)
COMBOS = [
    dict(OFF),                                           # every lever off
    dict(OFF, local_preprocessing=True),
    dict(OFF, coalesce=True),            # incl. the v-sorted index
    dict(OFF, coalesce=True, vsorted_index=False),  # slot-order v
    dict(OFF, src_only=True),
    dict(OFF, adaptive_doubling=True),
    dict(OFF, shrink_capacities=True),   # shrinking schedule alone
    dict(OFF, relabel_skip=True),        # settled-vertex RELABEL skip
    # the ghost_cache x coalesce x shrink_capacities sub-matrix
    # (the cache replaces the endpoint lookups, so each pairing takes a
    # genuinely different code path through _round_body)
    dict(OFF, ghost_cache=True),
    dict(OFF, ghost_cache=True, coalesce=True),
    dict(OFF, ghost_cache=True, shrink_capacities=True),
    dict(OFF, ghost_cache=True, coalesce=True, shrink_capacities=True),
    dict(ghost_cache=False, vsorted_index=False),  # slot-order optimized
    dict(ghost_cache=False),             # all levers minus the cache
    dict(shrink_capacities=False),       # all levers, flat capacities
    dict(),                              # everything incl. the schedule
    # the pallas_minedges lever: the fused kernel must be
    # bit-identical through every MINEDGES code path — the 2-exchange
    # baseline, the src-only per-run combine, ghost/vsorted reads, the
    # shrinking schedule, and the all-on engine
    dict(OFF, pallas_minedges=True),                     # 2-exchange kernel
    dict(OFF, src_only=True, pallas_minedges=True),      # fused combine
    dict(OFF, ghost_cache=True, coalesce=True, pallas_minedges=True),
    dict(shrink_capacities=False, pallas_minedges=True),  # flat + kernel
    dict(ghost_cache=False, vsorted_index=False, pallas_minedges=True),
    dict(pallas_minedges=True),          # everything through the kernel
]

fam = FAMILY
u, v, w, n = FAMILIES[fam](0)
edges = from_numpy(u, v, w, n)
kmask, kweight = oracle.kruskal(u, v, w, n)
for combo in COMBOS:
    mask, wt = minimum_spanning_forest(
        edges, algorithm="boruvka", engine="distributed_sharded",
        mesh=mesh, **combo)
    mk = np.asarray(mask)
    assert np.array_equal(np.nonzero(mk)[0], np.nonzero(kmask)[0]), (
        fam, combo, "edge set differs from oracle")
    assert abs(float(wt) - kweight) < 1e-3 * max(1.0, kweight), (
        fam, combo, float(wt), kweight)
print("OK")
"""


@pytest.mark.parametrize("family", ["random", "clustered", "dup_weights",
                                    "disconnected"])
def test_sharded_optimization_flags_match_oracle(family):
    # one process per family: on the CPU every compiled program holds
    # many code mappings, and all families' programs in one process
    # exhaust the kernel's map limit
    out = run_multidevice(f"FAMILY = {family!r}\n" + SHARDED_FLAGS, ndev=8,
                          timeout=1800)
    assert "OK" in out
