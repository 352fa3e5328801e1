"""Cross-engine oracle matrix: every MSF engine must produce the *unique*
(w, eid)-order MSF of the Kruskal oracle — same weight, same edge set.

Engines: static boruvka / filter_boruvka, dynamic boruvka /
filter_boruvka (in-process), distributed (replicated labels) and
distributed_sharded (1D-sharded labels + routed exchange) on 8 virtual
devices through the public ``minimum_spanning_forest`` dispatch
(subprocess; main process keeps 1 device).

Graph families (tests/helpers/graph_families.py, shared verbatim with
the subprocess): uniform random, clustered (RMAT), duplicate weights
(heavy ties — exercises the eid tie-break), disconnected (forest, not
tree), and self-loops lighter than every real edge (must never be
chosen).  Randomised over seeds; a hypothesis fuzz pass runs on top
when hypothesis is installed.
"""
import inspect

import numpy as np
import pytest

from repro.core import oracle
from repro.core.boruvka import boruvka_msf
from repro.core.filter_boruvka import (boruvka_dynamic,
                                       filter_boruvka_dynamic,
                                       filter_boruvka_msf)
from tests.helpers import graph_families
from tests.helpers.graph_families import FAMILIES
from tests.helpers.hypothesis_compat import given, settings, st
from tests.helpers.subproc import run_multidevice


ENGINES = {
    "boruvka_msf": lambda u, v, w, n: boruvka_msf(u, v, w, n)[0],
    "filter_boruvka_msf":
        lambda u, v, w, n: filter_boruvka_msf(u, v, w, n, num_buckets=4)[0],
    "boruvka_dynamic": lambda u, v, w, n: boruvka_dynamic(u, v, w, n)[0],
    "filter_boruvka_dynamic":
        lambda u, v, w, n: filter_boruvka_dynamic(u, v, w, n)[0],
}


def _assert_matches_oracle(mask, u, v, w, n, ctx):
    kmask, kweight = oracle.kruskal(u, v, w, n)
    mask = np.asarray(mask)
    assert np.array_equal(np.nonzero(mask)[0], np.nonzero(kmask)[0]), (
        ctx, "edge set differs from the (w, eid) oracle MSF")
    got = float(np.sum(w[mask]))
    assert abs(got - kweight) < 1e-3 * max(1.0, kweight), (ctx, got, kweight)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_local_engines_match_oracle(family, engine, seed):
    u, v, w, n = FAMILIES[family](seed)
    mask = ENGINES[engine](u, v, w, n)
    _assert_matches_oracle(mask, u, v, w, n, (family, engine, seed))


# --------------------------------------------------------------------------
# distributed engines (8 virtual devices >= 4 shards, subprocess)
# --------------------------------------------------------------------------

# the exact same family builders, injected as source so the two matrices
# cannot drift apart
DISTRIBUTED = inspect.getsource(graph_families) + """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.graph import from_numpy
from repro.core.mst import minimum_spanning_forest

mesh = Mesh(np.array(jax.devices()), ("data",))

for fam, make in sorted(FAMILIES.items()):
    u, v, w, n = make(0)
    edges = from_numpy(u, v, w, n)
    kmask, kweight = oracle.kruskal(u, v, w, n)
    for engine in ("distributed", "distributed_sharded"):
        for algo in ("boruvka", "filter_boruvka"):
            mask, wt = minimum_spanning_forest(
                edges, algorithm=algo, engine=engine, mesh=mesh)
            mk = np.asarray(mask)
            assert np.array_equal(np.nonzero(mk)[0], np.nonzero(kmask)[0]), (
                fam, engine, algo, "edge set differs from oracle")
            assert abs(float(wt) - kweight) < 1e-3 * max(1.0, kweight), (
                fam, engine, algo, float(wt), kweight)
print("OK")
"""


def test_distributed_engines_match_oracle():
    out = run_multidevice(DISTRIBUTED, ndev=8, timeout=1800)
    assert "OK" in out


# plan measured with the kernel lever, replayed strictly (replan=False)
# through the Python-unrolled executor with the ISSUE 7 self-verifier on:
# pins (a) the lever survives the RoundPlan round-trip, (b) replay is
# bit-identical to the oracle through the kernel path, (c) verify=True
# accepts the kernel-path forest
SHARDED_PALLAS_PLAN = inspect.getsource(graph_families) + """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import (execute_plan,
                                            plan_sharded_msf)
from repro.core.plan import RoundPlan

mesh = Mesh(np.array(jax.devices()), ("data",))
for fam in ("dup_weights", "disconnected"):
    u, v, w, n = FAMILIES[fam](0)
    kmask, kweight = oracle.kruskal(u, v, w, n)
    g, cap = build_dist_graph(u, v, w, n, 8)
    plan = plan_sharded_msf(g, n, mesh, pallas_minedges=True)
    assert plan.pallas_minedges
    plan = RoundPlan.from_json(plan.to_json())  # lever round-trips
    assert plan.pallas_minedges
    mask, wt, cnt, lab, ovf, comm = execute_plan(
        g, n, mesh, plan, replan=False, verify=True)
    assert int(ovf) == 0, (fam, int(ovf))
    got = sorted(set(int(e) for e in np.asarray(g.eid)[np.asarray(mask)]))
    assert got == sorted(np.nonzero(kmask)[0].tolist()), (
        fam, "edge set differs from oracle through the kernel plan path")
    assert abs(float(wt) - kweight) < 1e-3 * max(1.0, kweight)
print("OK")
"""


def test_sharded_pallas_plan_replay_verified():
    out = run_multidevice(SHARDED_PALLAS_PLAN, ndev=8, timeout=1800)
    assert "OK" in out


# ISSUE 10: the two-level grid ghost push.  On a (4, 2) mesh every
# family must be bit-identical across flat push x grid push x the
# public dispatch default, the ghost_shard_limit ladder must step
# grid -> flat -> no-ghost without changing a single mask bit, and the
# grid lever must survive the RoundPlan JSON round-trip, show up in
# plan_cache_key, and replay strictly (replan=False) bit-identical.
SHARDED_GRID_PUSH = inspect.getsource(graph_families) + """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import (distributed_sharded_msf,
                                            execute_plan, plan_sharded_msf)
from repro.core.graph import from_numpy
from repro.core.mst import minimum_spanning_forest
from repro.core.plan import RoundPlan

mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("row", "col"))
AX = ("row", "col")

for fam in ("random", "dup_weights", "disconnected"):
    u, v, w, n = FAMILIES[fam](0)
    edges = from_numpy(u, v, w, n)
    kmask, kweight = oracle.kruskal(u, v, w, n)
    ref = None
    for push in (None, "flat", "grid"):
        mask, wt = minimum_spanning_forest(
            edges, algorithm="boruvka", engine="distributed_sharded",
            mesh=mesh, axis_names=AX, ghost_push=push)
        mk = np.asarray(mask)
        assert np.array_equal(np.nonzero(mk)[0], np.nonzero(kmask)[0]), (
            fam, push, "edge set differs from oracle")
        assert abs(float(wt) - kweight) < 1e-3 * max(1.0, kweight), (
            fam, push, float(wt), kweight)
        if ref is None:
            ref = mk
        assert np.array_equal(mk, ref), (fam, push, "flat/grid drift")

# ghost_shard_limit fallback ladder on the same 2-axis mesh: a limit
# of 31 fits p=8 in one flat mask (no grid rounds), 7 forces the grid
# rung (4 <= 7 and 2 <= 7 but p=8 > 7), 1 disables the cache entirely
# (rows 4 > 1) — every rung bit-identical, overflow 0
u, v, w, n = FAMILIES["random"](1)
g, cap = build_dist_graph(u, v, w, n, 8)
kmask, _ = oracle.kruskal(u, v, w, n)
ksel = np.nonzero(kmask)[0]
base = None
for lim, expect_hits, expect_grid in ((31, True, False),
                                      (7, True, True),
                                      (1, False, False)):
    tr = []
    res = distributed_sharded_msf(g, n, mesh, axis_names=AX,
                                  ghost_shard_limit=lim, round_trace=tr)
    assert int(res[4]) == 0, (lim, int(res[4]))
    sel = np.unique(np.asarray(g.eid)[np.asarray(res[0])])
    assert np.array_equal(sel, ksel), (lim, "edge set != oracle")
    if base is None:
        base = np.asarray(res[0])
    assert np.array_equal(np.asarray(res[0]), base), (lim, "ladder drift")
    hits = float(res[5].hits)
    assert (hits > 0) == expect_hits, (lim, hits)
    grid_rounds = any(t.get("grid_push") for t in tr)
    assert grid_rounds == expect_grid, (lim, grid_rounds)

# the plan lever: measured grid plan carries per-round deputy
# capacities, round-trips to_json/from_json, keys differently from the
# flat plan, and replays strictly bit-identical (incl. after pad())
plan = plan_sharded_msf(g, n, mesh, axis_names=AX, ghost_push="grid")
assert plan.grid_push
assert any(r.cap_push_col > 0 for r in plan.rounds)
rt = RoundPlan.from_json(plan.to_json())
assert rt == plan, "grid lever lost in the JSON round-trip"
assert plan.cache_key("x") != plan._replace(grid_push=False).cache_key("x")
for p2 in (rt, rt.pad(0.25)):
    res = execute_plan(g, n, mesh, p2, axis_names=AX, replan=False)
    assert int(res[4]) == 0
    assert np.array_equal(np.asarray(res[0]), base), "replay drift"
print("OK")
"""


def test_sharded_grid_push_matrix():
    out = run_multidevice(SHARDED_GRID_PUSH, ndev=8, timeout=1800)
    assert "OK" in out


# p = 32 (8 x 4) — impossible at seed: the flat int32 subscriber mask
# caps the ghost cache at 31 shards, so before ISSUE 10 the cache was
# forced off here.  The auto ladder must now pick the grid push, keep
# the cache live (hits > 0), and stay bit-identical to the oracle.
SHARDED_GRID_P32 = """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import distributed_sharded_msf
from repro.data import generators

mesh = Mesh(np.array(jax.devices()).reshape(8, 4), ("row", "col"))
u, v, w, n = generators.generate("rgg2d", 1024, avg_degree=8.0, seed=7)
g, cap = build_dist_graph(u, v, w, n, 32)
kmask, _ = oracle.kruskal(u, v, w, n)
tr = []
res = distributed_sharded_msf(g, n, mesh, axis_names=("row", "col"),
                              round_trace=tr)
assert int(res[4]) == 0, int(res[4])
sel = np.unique(np.asarray(g.eid)[np.asarray(res[0])])
assert np.array_equal(sel, np.nonzero(kmask)[0]), "edge set != oracle"
assert float(res[5].hits) > 0, "cache must be live at p=32"
assert any(t["grid_push"] for t in tr), "auto ladder must pick grid"
print("OK")
"""


def test_sharded_grid_push_p32_oracle():
    out = run_multidevice(SHARDED_GRID_P32, ndev=32, timeout=1800)
    assert "OK" in out


# every engine whose CONTRACT runs dense pointer doubling (stopping at the
# first pass that changes nothing): static Borůvka and Filter-Borůvka, the
# replicated engine's three rounds (preprocessing on, so its contraction
# core doubles too) and the sharded engine's preprocessing, whose shards
# stop their doubling loops at different passes
DENSE_DOUBLING = """
from jax.sharding import Mesh
from repro import obs
from repro.core import oracle
from repro.core.boruvka import boruvka_msf
from repro.core.distributed import build_dist_graph, distributed_msf
from repro.core.distributed_sharded import distributed_sharded_msf
from repro.core.filter_boruvka import filter_boruvka_msf
from repro.data import generators

mesh = Mesh(np.array(jax.devices()), ("data",))
if FAMILY == "rgg2d":
    u, v, w, n = generators.rgg2d(4096, 8.0, 11)
else:
    u, v, w, n = generators.rmat(12, 16 * 4096, 11)
want = oracle.kruskal_fast(u, v, w, n)
ju, jv, jw = jnp.asarray(u), jnp.asarray(v), jnp.asarray(w)
got = {"boruvka_msf": boruvka_msf(ju, jv, jw, n)[0],
       "filter_boruvka_msf": filter_boruvka_msf(ju, jv, jw, n)[0]}
g, cap = build_dist_graph(u, v, w, n, 8)
for algo in ("boruvka", "filter_boruvka", "boruvka_shrink"):
    res = distributed_msf(g, n, mesh, algorithm=algo)
    got["distributed/" + algo] = res[0]
obs.clear()
res = distributed_sharded_msf(g, n, mesh)
assert int(res[4]) == 0, int(res[4])
got["distributed_sharded"] = res[0]
(rec,) = obs.solve_records()
# the preprocessing program's passes, at least one a round
assert rec["doubling_steps"] > 0, rec
for name, mask in got.items():
    mask = np.asarray(mask)
    if name.startswith("distributed"):
        sel = np.unique(np.asarray(g.eid)[mask])
        mask = np.zeros(len(u), bool)
        mask[sel] = True
    assert np.array_equal(mask, want), (FAMILY, name)
print("OK")
"""


@pytest.mark.parametrize("family", ["rgg2d", "kronecker"])
def test_dense_doubling_engines_exact(family):
    out = run_multidevice(f"FAMILY = {family!r}\n" + DENSE_DOUBLING,
                          ndev=8, timeout=1800)
    assert "OK" in out


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_property_random_graphs_match_oracle(data):
    n = data.draw(st.integers(2, 40), label="n")
    m = data.draw(st.integers(0, 120), label="m")
    seed = data.draw(st.integers(0, 2 ** 31 - 1), label="seed")
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m).astype(np.int32)
    v = rng.integers(0, n, m).astype(np.int32)
    # intentionally keep self-loops and parallel edges
    w = rng.integers(1, 8, m).astype(np.float32)
    for engine, fn in sorted(ENGINES.items()):
        mask = fn(u, v, w, n)
        _assert_matches_oracle(mask, u, v, w, n, (engine, n, m, seed))
