"""The plain reference and the comparison that decides ``correct``.

``msf_mask`` is the exact minimum spanning forest under the total order
``(w, edge index)``: the unique forest every engine of the program must
return.  It is ``repro/core/oracle.py: kruskal_fast``, copied so that it
imports nothing of the program: rank the edges by ``(w, eid)``, keep the
lowest-ranked edge of each vertex pair, and let scipy's minimum spanning
tree pick the forest of the (distinct) ranks.  (The copy groups the
pairs with one stable sort where the original lexsorts; the result is
the same, and ``tests/test_reference.py`` holds it to the plain Kruskal
loop.)

``compare`` holds every forest a run's window produced against it, slot
for slot, padding included: an exact comparison, so each limit is 0.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def msf_mask(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int
             ) -> np.ndarray:
    """Mask over the input edges of the ``(w, eid)``-ordered MSF."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import minimum_spanning_tree

    m = len(u)
    order = np.argsort(w, kind="stable")  # (w, eid): ties by index
    rank = np.empty(m, np.int64)
    rank[order] = np.arange(1, m + 1)  # 1-based: scipy reads 0 as no edge
    a = np.minimum(u, v).astype(np.int64)
    b = np.maximum(u, v).astype(np.int64)
    live = order[np.isfinite(w[order]) & (a[order] != b[order])]
    # grouped by vertex pair, by rank within a pair: a stable sort of
    # the live edges, taken in rank order, by pair
    pair = a[live] * n + b[live]
    by_pair = live[np.argsort(pair, kind="stable")]
    pair = a[by_pair] * n + b[by_pair]
    first = np.ones(len(by_pair), bool)
    first[1:] = pair[1:] != pair[:-1]
    cand = by_pair[first]
    g = coo_matrix((rank[cand].astype(np.float64), (a[cand], b[cand])),
                   shape=(n, n)).tocsr()
    tree = minimum_spanning_tree(g).tocoo()
    mask = np.zeros(m, bool)
    mask[order[tree.data.astype(np.int64) - 1]] = True
    return mask


def compare(forests: Sequence[np.ndarray], want: np.ndarray,
            overflows: Sequence[int] = ()) -> Dict[str, Dict[str, float]]:
    """Numbers compared, each with its limit.

    ``forests`` are the host edge masks the timed solves returned, over
    the solve's edge slots (padding slots past ``len(want)`` must be
    empty).  ``wrong_forests`` counts the solves whose forest is not
    exactly ``want``; ``wrong_edges_max`` is the most edges by which one
    of them differs; ``overflow`` sums the exchange items an engine
    reports dropped (a result is exact only at 0).
    """
    m = len(want)
    diffs: List[int] = []
    for got in forests:
        got = np.asarray(got, bool)
        if len(got) < m:  # a short mask leaves the rest of the edges out
            got = np.concatenate([got, np.zeros(m - len(got), bool)])
        diffs.append(int(np.count_nonzero(got[:m] != want))
                     + int(np.count_nonzero(got[m:])))
    return {
        "solves_checked": {"value": len(diffs), "limit": 1},
        "wrong_forests": {"value": sum(d > 0 for d in diffs), "limit": 0},
        "wrong_edges_max": {"value": max(diffs, default=0), "limit": 0},
        "overflow": {"value": int(sum(overflows)), "limit": 0},
    }


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    """``solves_checked`` is a floor; every other number a ceiling."""
    for name, c in checks.items():
        if name == "solves_checked":
            if c["value"] < c["limit"]:
                return False
        elif c["value"] > c["limit"]:
            return False
    return True
