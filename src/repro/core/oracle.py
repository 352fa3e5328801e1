"""Sequential MSF oracle (Kruskal + union-find), host-side numpy.

Used as the ground truth for every correctness test and to validate the
distributed/jittable engines.  Tie-breaking matches the JAX engines:
lexicographic on (weight, edge index) which yields a unique MSF.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


class UnionFind:
    def __init__(self, n: int):
        self.parent = np.arange(n)

    def find(self, x: int) -> int:
        root = x
        p = self.parent
        while p[root] != root:
            root = p[root]
        while p[x] != root:  # path compression
            p[x], x = root, p[x]
        return root

    def union(self, a: int, b: int) -> bool:
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return False
        self.parent[max(ra, rb)] = min(ra, rb)
        return True


def kruskal(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int
            ) -> Tuple[np.ndarray, float]:
    """Return (mask over input edges, total MSF weight)."""
    m = len(u)
    finite = np.isfinite(w)
    idx = np.arange(m)
    order = np.lexsort((idx, w))  # (w, idx) lexicographic
    uf = UnionFind(n)
    mask = np.zeros(m, bool)
    total = 0.0
    for e in order:
        if not finite[e] or u[e] == v[e]:
            continue
        if uf.union(int(u[e]), int(v[e])):
            mask[e] = True
            total += float(w[e])
    return mask, total


def kruskal_fast(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int
                 ) -> np.ndarray:
    """The same (w, eid)-ordered MSF as ``kruskal``, vectorised.

    Edges are ranked by ``(w, eid)``; of each vertex pair only the
    min-rank edge can be in the forest, and the forest of the unique
    ranks is scipy's minimum spanning tree.  Independent of the engines
    and exact for graphs too large for the Python loop.  Returns the
    mask over input edges.
    """
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import minimum_spanning_tree

    m = len(u)
    order = np.lexsort((np.arange(m), w))
    rank = np.empty(m, np.int64)
    rank[order] = np.arange(1, m + 1)  # 1-based: scipy reads 0 as no edge
    a = np.minimum(u, v).astype(np.int64)
    b = np.maximum(u, v).astype(np.int64)
    live = np.nonzero(np.isfinite(w) & (a != b))[0]
    key = a[live] * n + b[live]
    by_pair = live[np.lexsort((rank[live], key))]
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


def msf_weight(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int) -> float:
    return kruskal(u, v, w, n)[1]


def component_labels(u: np.ndarray, v: np.ndarray, n: int) -> np.ndarray:
    """Connected-component representative for each vertex (min vertex id)."""
    uf = UnionFind(n)
    for a, b in zip(u, v):
        uf.union(int(a), int(b))
    return np.array([uf.find(i) for i in range(n)], np.int32)


def is_forest(u: np.ndarray, v: np.ndarray, n: int) -> bool:
    uf = UnionFind(n)
    for a, b in zip(u, v):
        if not uf.union(int(a), int(b)):
            return False
    return True
