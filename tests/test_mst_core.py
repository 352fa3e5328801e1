"""Core MSF correctness: jittable Borůvka + Filter-Borůvka vs Kruskal oracle."""
import numpy as np
import pytest

from tests.helpers.hypothesis_compat import given, settings, st

from repro.core import oracle
from repro.core.boruvka import boruvka_msf
from repro.core.filter_boruvka import (boruvka_dynamic,
                                       filter_boruvka_dynamic,
                                       filter_boruvka_msf)
from repro.core.graph import from_numpy
from repro.core.mst import minimum_spanning_forest
from repro.data import generators


def _random_graph(n, m, seed, int_weights=False):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m).astype(np.int32)
    v = rng.integers(0, n, m).astype(np.int32)
    keep = u != v
    u, v = u[keep], v[keep]
    if int_weights:  # many ties
        w = rng.integers(1, 8, len(u)).astype(np.float32)
    else:
        w = rng.uniform(1, 255, len(u)).astype(np.float32)
    return u, v, w


def _check(u, v, w, n, mask):
    mask = np.asarray(mask)
    _, expect = oracle.kruskal(u, v, w, n)
    got = float(w[mask].sum())
    assert got == pytest.approx(expect, rel=1e-5), (got, expect)
    # forest invariant
    assert oracle.is_forest(u[mask], v[mask], n)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("algo", ["boruvka", "filter_boruvka"])
def test_static_engine_random(seed, algo):
    u, v, w = _random_graph(200, 800, seed)
    edges = from_numpy(u, v, w, 200)
    mask, wt = minimum_spanning_forest(edges, algorithm=algo, engine="static")
    _check(u, v, w, 200, mask)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("algo", ["boruvka", "filter_boruvka"])
def test_dynamic_engine_random(seed, algo):
    u, v, w = _random_graph(300, 1500, seed)
    edges = from_numpy(u, v, w, 300)
    mask, wt = minimum_spanning_forest(edges, algorithm=algo, engine="dynamic")
    _check(u, v, w, 300, np.asarray(mask))


@pytest.mark.parametrize("algo", ["boruvka", "filter_boruvka"])
def test_ties(algo):
    """Heavily tied integer weights must still give the oracle weight."""
    u, v, w = _random_graph(100, 600, 7, int_weights=True)
    edges = from_numpy(u, v, w, 100)
    mask, _ = minimum_spanning_forest(edges, algorithm=algo, engine="static")
    _check(u, v, w, 100, mask)


def test_padding_is_ignored():
    u, v, w = _random_graph(50, 200, 3)
    edges = from_numpy(u, v, w, 50, pad_to=512)
    mask, wt = minimum_spanning_forest(edges, engine="static")
    _, expect = oracle.kruskal(u, v, w, 50)
    assert float(wt) == pytest.approx(expect, rel=1e-5)
    assert not np.asarray(mask)[len(u):].any()


def test_disconnected_forest():
    # two cliques, no crossing edges
    rng = np.random.default_rng(0)
    u1, v1 = np.triu_indices(10, 1)
    u2, v2 = u1 + 10, v1 + 10
    u = np.concatenate([u1, u2]).astype(np.int32)
    v = np.concatenate([v1, v2]).astype(np.int32)
    w = rng.uniform(1, 255, len(u)).astype(np.float32)
    edges = from_numpy(u, v, w, 20)
    mask, wt = minimum_spanning_forest(edges, engine="static")
    assert int(np.asarray(mask).sum()) == 18  # (10-1) * 2
    _check(u, v, w, 20, mask)


def test_single_edge_and_empty():
    edges = from_numpy(np.array([0], np.int32), np.array([1], np.int32),
                       np.array([3.0], np.float32), 2)
    mask, wt = minimum_spanning_forest(edges, engine="static")
    assert bool(np.asarray(mask)[0]) and float(wt) == 3.0
    empty = from_numpy(np.zeros(0, np.int32), np.zeros(0, np.int32),
                       np.zeros(0, np.float32), 4, pad_to=8)
    mask, wt = minimum_spanning_forest(empty, engine="static")
    assert float(wt) == 0.0


@pytest.mark.parametrize("family", ["grid2d", "gnm", "rmat", "rgg2d"])
def test_generated_families(family):
    u, v, w, n = generators.generate(family, 1024, avg_degree=8.0, seed=1)
    edges = from_numpy(u, v, w, n)
    for algo in ("boruvka", "filter_boruvka"):
        mask, _ = minimum_spanning_forest(edges, algorithm=algo,
                                          engine="static")
        _check(u, v, w, n, mask)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 60), st.integers(1, 300), st.integers(0, 10_000),
       st.booleans())
def test_property_engines_agree(n, m, seed, ties):
    """Hypothesis: all engines produce the oracle MSF weight."""
    u, v, w = _random_graph(n, m, seed, int_weights=ties)
    if len(u) == 0:
        return
    edges = from_numpy(u, v, w, n)
    _, expect = oracle.kruskal(u, v, w, n)
    for algo in ("boruvka", "filter_boruvka"):
        mask, wt = minimum_spanning_forest(edges, algorithm=algo,
                                           engine="static")
        assert float(wt) == pytest.approx(expect, rel=1e-5)
    mask_d, wt_d = filter_boruvka_dynamic(u, v, w, n, min_edges=16)
    assert wt_d == pytest.approx(expect, rel=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 40), st.integers(1, 150), st.integers(0, 10_000))
def test_property_unique_msf_edges_match(n, m, seed):
    """With distinct weights the exact edge set must match the oracle."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m).astype(np.int32)
    v = rng.integers(0, n, m).astype(np.int32)
    keep = u != v
    u, v = u[keep], v[keep]
    if len(u) == 0:
        return
    w = rng.permutation(len(u)).astype(np.float32) + 1.0  # distinct
    edges = from_numpy(u, v, w, n)
    emask, _ = oracle.kruskal(u, v, w, n)
    # distinct weights => unique MSF => identical masks modulo duplicate
    # (u,v,w) triples; compare weights-sorted multiset instead of indices
    for algo in ("boruvka", "filter_boruvka"):
        mask, _ = minimum_spanning_forest(edges, algorithm=algo,
                                          engine="static")
        got = np.sort(w[np.asarray(mask)])
        exp = np.sort(w[emask])
        assert np.allclose(got, exp)


@pytest.mark.parametrize("family", ["random", "ties_loops_inf", "rgg2d"])
def test_kruskal_fast_matches_kruskal(family):
    """The vectorised oracle picks the loop oracle's exact (w, eid) edge
    set: parallel edges, self-loops, heavy weight ties and +inf padding."""
    for seed in range(40 if family != "rgg2d" else 2):
        rng = np.random.default_rng(seed)
        if family == "rgg2d":
            u, v, w, n = generators.rgg2d(2048, 8.0, seed)
        else:
            n = int(rng.integers(1, 40))
            m = int(rng.integers(0, 150))
            u = rng.integers(0, n, m).astype(np.int32)
            v = rng.integers(0, n, m).astype(np.int32)
            if family == "random":
                w = rng.uniform(1, 255, m).astype(np.float32)
            else:
                w = rng.integers(1, 6, m).astype(np.float32)
                w[rng.random(m) < 0.1] = np.inf
        want, _ = oracle.kruskal(u, v, w, n)
        assert np.array_equal(oracle.kruskal_fast(u, v, w, n), want), seed
