"""The reference against the plain Kruskal loop of the repo's oracle, on
graphs with heavy weight ties, parallel edges, self-loops and padding."""
import numpy as np
import pytest

from bench import reference
from repro.core import oracle


@pytest.mark.parametrize("seed", range(6))
def test_reference_is_kruskal(seed):
    rng = np.random.default_rng(seed)
    n, m = 300, 1500
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    w = rng.integers(1, 6, m).astype(np.float32)  # many ties
    w[rng.random(m) < 0.05] = np.inf  # padding slots
    want, _ = oracle.kruskal(u, v, w, n)
    np.testing.assert_array_equal(reference.msf_mask(u, v, w, n), want)


def test_compare_counts_padding_and_short_masks():
    want = np.array([True, False, True])
    checks = reference.compare([np.array([True, False, True, False]),
                                np.array([True, False, True, True]),
                                np.array([False, False, True, False]),
                                np.array([True])],
                               want, overflows=[0, 0, 2, 0])
    assert checks["solves_checked"]["value"] == 4
    assert checks["wrong_forests"]["value"] == 3
    assert checks["wrong_edges_max"]["value"] == 1
    assert checks["overflow"]["value"] == 2
    assert not reference.passed(checks)
