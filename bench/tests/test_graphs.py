"""The benchmark's generators: the copies keep the repo generators'
graphs, every seed labels one structure anew, and every pinned slot
count holds the configuration's graph."""
import numpy as np
import pytest

from bench import harness, reference
from bench.tests import sizes
from repro.data import generators

LARGE_SEED = 2**33 + 12345
KRON = harness.plugin("generators", "graph500_kronecker")
RGG = harness.plugin("generators", "kagen_rgg2d")


@pytest.mark.parametrize("n,seed", [(1 << 12, 0), (1 << 13, LARGE_SEED)])
def test_rgg2d_structure_is_the_repo_generator(n, seed):
    got, _, _ = RGG.structure(n, 8.0, seed)
    want = generators.rgg2d(n, 8.0, seed)
    assert got[3] == want[3]
    for a, b in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(a, b)


def _degrees(u, v, n):
    return np.bincount(np.concatenate([u, v]), minlength=n)


def test_kronecker_degree_skew_matches_rmat():
    """Device Kronecker draws against the numpy RMAT one: the same edge
    count after dedup and the same skew (hub degree, share of the edges
    at the top 1% of vertices), within sampling noise."""
    scale, ef = 13, 16
    u, v, w, n = KRON.kronecker(scale, ef, 7, 8)
    ru, rv, rw, rn = generators.rmat(scale, ef << scale, 7)
    assert n == rn
    assert abs(len(u) - len(ru)) < 0.02 * len(ru)
    d, rd = np.sort(_degrees(u, v, n)), np.sort(_degrees(ru, rv, rn))
    top = n // 100
    share, rshare = d[-top:].sum() / d.sum(), rd[-top:].sum() / rd.sum()
    assert abs(share - rshare) < 0.03, (share, rshare)
    assert 0.7 < d[-1] / rd[-1] < 1.4, (d[-1], rd[-1])
    assert (d == 0).mean() == pytest.approx((rd == 0).mean(), abs=0.02)


GRAPHS = [lambda s: KRON.kronecker(10, 16, 3, s),
          lambda s: RGG.rgg2d(1 << 12, 8.0, 3, s)]


@pytest.mark.parametrize("make", GRAPHS, ids=["kronecker", "rgg2d"])
def test_edge_lists_are_canonical(make):
    u, v, w, n = make(5)
    assert (u < v).all() and (v < n).all()
    key = u.astype(np.int64) * n + v
    assert (np.diff(key) > 0).all()  # sorted, no parallel edges
    assert w.dtype == np.float32
    assert ((w >= 1.0) & (w <= 255.0)).all()


@pytest.mark.parametrize("make", GRAPHS, ids=["kronecker", "rgg2d"])
def test_seeds_label_one_structure(make):
    """Two seeds: new edge lists of one structure, so the same degree
    sequence, weights and forest weight, and different forest edges."""
    a, b = make(2**31 + 1), make(2**31 + 1 + 2**32)
    assert not np.array_equal(a[0], b[0])
    assert len(a[0]) == len(b[0])
    np.testing.assert_array_equal(np.sort(_degrees(*a[:2], a[3])),
                                  np.sort(_degrees(*b[:2], b[3])))
    np.testing.assert_array_equal(np.sort(a[2]), np.sort(b[2]))
    fa, fb = reference.msf_mask(*a), reference.msf_mask(*b)
    assert a[2][fa].sum(dtype=np.float64) == b[2][fb].sum(dtype=np.float64)
    assert not np.array_equal(fa, fb)


def test_rgg2d_labels_keep_locality():
    u, v, w, n = RGG.rgg2d(1 << 14, 8.0, 0, 9)
    (su, sv, _, _), _, _ = RGG.structure(1 << 14, 8.0, 0)
    # ids follow the cells, so edges stay short in id space
    assert np.median(v - u) < 4 * np.median(sv - su)
    assert np.median(v - u) < n / 50


@pytest.mark.parametrize("name", [w["name"] for w in
                                  harness.benchmark_spec()["workloads"]])
def test_pinned_slots_hold_the_graph(name):
    """Every seed's graph fits the cell's pinned slots: the generator
    bounds the edges of any seed."""
    cell = harness.load("workloads", name)
    cfg = harness.load("configs", cell["config"])
    per = harness.load("traffic", cell["traffic"]).get(
        "slots_per_edge", 1) / cell["chips"]
    bound = harness.plugin("generators", cfg["generator"]).edge_bound(cfg)
    assert bound * per <= cell["slots"]


@pytest.mark.parametrize("name", [c["name"] for c in
                                  harness.benchmark_spec()["configs"]])
def test_edge_bound_holds_every_seed(name):
    cfg = dict(harness.load("configs", name), **sizes.shrink(name))
    gen = harness.plugin("generators", cfg["generator"])
    bound = gen.edge_bound(cfg)
    assert all(len(gen.generate(cfg, s)[0]) <= bound
               for s in (1, 2**32 + 3, 77))


def test_too_small_a_pin_is_refused():
    import time
    import jax
    with pytest.raises(harness.BenchError, match="pinned"):
        harness.run("rgg20.boruvka", 1, 0.1, False, time.perf_counter(),
                    devices=jax.devices(), cfg_override={"log2_n": 10},
                    slots_override=1000)
