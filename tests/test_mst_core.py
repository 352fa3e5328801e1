"""Core MSF correctness: jittable Borůvka + Filter-Borůvka vs Kruskal oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from tests.helpers.hypothesis_compat import given, settings, st

from repro.core import oracle
from repro.core.boruvka import (_doubling_iters, boruvka_msf,
                                boruvka_msf_counted, pointer_double)
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


# --------------------------------------------------------------------------
# CONTRACT's pointer doubling: stops at the first pass that changes nothing
# --------------------------------------------------------------------------

def _parent_array(kind, n, seed):
    rng = np.random.default_rng(seed)
    if kind == "forest":  # every node points at an earlier one, or itself
        perm = rng.permutation(n)
        up = (rng.random(n) * np.arange(n)).astype(np.int64)
        par = np.where(rng.random(n) < 0.1, np.arange(n), up)
        out = np.empty(n, np.int32)
        out[perm] = perm[par]
        return out
    if kind == "pseudoforest":  # one cycle per component, of any length
        return rng.integers(0, n, n).astype(np.int32)
    return np.minimum(np.arange(n) + 1, n - 1).astype(np.int32)  # path


def _depth(par):
    depth = np.zeros(len(par), np.int64)
    p = par.copy()
    while (p != par[p]).any():
        depth += p != par[p]
        p = par[p]
    return int((depth + (par != np.arange(len(par)))).max())


@pytest.mark.parametrize("kind,n,seed", [
    ("forest", 1000, 0), ("forest", 4096, 1), ("forest", 3, 2),
    ("pseudoforest", 1000, 3), ("pseudoforest", 4096, 4), ("path", 513, 0)])
def test_pointer_double_matches_fixed_schedule(kind, n, seed):
    """The same roots as ``_doubling_iters(n)`` passes of ``p = p[p]``; on
    a forest of depth d, ceil(log2 d) + 1 passes (the cap at most)."""
    par = _parent_array(kind, n, seed)
    want = par.copy()
    for _ in range(_doubling_iters(n)):
        want = want[want]
    roots, steps = pointer_double(jnp.asarray(par), n)
    assert np.array_equal(np.asarray(roots), want)
    if kind != "pseudoforest":
        d = _depth(par)
        need = int(np.ceil(np.log2(d))) + 1 if d else 1
        assert int(steps) == min(need, _doubling_iters(n))
    assert 1 <= int(steps) <= _doubling_iters(n)


@pytest.mark.parametrize("k", [2, 5, 10])
@pytest.mark.parametrize("algo", ["boruvka", "filter_boruvka"])
def test_deep_path_converges_within_cap(k, algo):
    """A path of 2^k vertices whose weights fall along it: every vertex
    chooses its next edge, so round 1 builds one tree of depth 2^k - 2,
    which needs all ``_doubling_iters(2^k) = k`` passes."""
    n = 1 << k
    u = np.arange(n - 1, dtype=np.int32)
    v = u + 1
    w = (n - u).astype(np.float32)
    edges = from_numpy(u, v, w, n)
    mask, _ = minimum_spanning_forest(edges, algorithm=algo,
                                      num_buckets=1)
    want, _ = oracle.kruskal(u, v, w, n)
    assert np.array_equal(np.asarray(mask), want)
    _, labels, counters = boruvka_msf_counted(edges.u, edges.v, edges.w, n)
    assert len(set(np.asarray(labels).tolist())) == 1
    # round 1 runs to the cap; round 2 finds every edge internal
    assert (int(counters["rounds"]), int(counters["doubling_steps"])) == \
        (2, k + 1)


def test_doubling_steps_hand_count():
    """Two paths 0-1-2-3 and 4-5-6-7, weights falling along each, and a
    heavy bridge 3-4.  Round 1: trees 0->1->2<->3 and 4->5->6<->7 (the
    2-cycles keep 2 and 6 as roots), depth 2: one pass flattens, one
    finds nothing.  Round 2: both components choose the bridge, 2 <-> 6
    with 2 as root, depth 1: one pass.  Round 3 finds no edge: one pass.
    """
    u = np.array([0, 1, 2, 4, 5, 6, 3], np.int32)
    v = np.array([1, 2, 3, 5, 6, 7, 4], np.int32)
    w = np.array([3, 2, 1, 13, 12, 11, 20], np.float32)
    mask, labels, counters = boruvka_msf_counted(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(w), 8)
    assert np.asarray(mask).all() and set(np.asarray(labels)) == {2}
    assert int(counters["rounds"]) == 3
    assert int(counters["doubling_steps"]) == 2 + 1 + 1
