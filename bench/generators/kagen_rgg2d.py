"""2D random geometric graphs, KaGen's RGG2D
(``"generator": "kagen_rgg2d"``).

The points are binned into cells of side ``>= r``, and each point is
checked against every point of five neighbour cells (a half-plane of
the eight, and its own), in numpy, vectorised over all points; the
structure and weights are those of ``repro.data.generators.rgg2d``.
The run's labelling keeps the ids in cell order, as KaGen's output is:
one of the square's eight symmetries orders the cells, and the points
of a cell are shuffled.  Configuration keys: ``log2_n``, ``avg_degree``,
``structure_seed``.
"""
from __future__ import annotations

import math

import numpy as np

from bench.graphs import Edges, assign_weights, relabel

# the five cell offsets of a half-plane: each unordered pair of
# neighbouring cells is visited once, a cell's own pairs with (0, 0)
_HALF_OFFSETS = ((0, 0), (0, 1), (1, -1), (1, 0), (1, 1))


def structure(n: int, avg_degree: float, structure_seed: int):
    """2D random geometric graph in the unit square, radius
    ``sqrt(avg_degree / (pi n))``; vertex ids follow the cells' order.

    Returns the edges and each vertex's cell ``(cx, cy)`` of ``ncell``
    per side."""
    rng = np.random.default_rng(structure_seed)
    r = math.sqrt(avg_degree / (math.pi * n))
    pts = rng.random((n, 2))
    ncell = max(1, int(1.0 / r))
    cell = np.minimum((pts * ncell).astype(np.int64), ncell - 1)
    key = cell[:, 0] * ncell + cell[:, 1]
    order = np.argsort(key, kind="stable")
    x, y, cell, key = pts[order, 0], pts[order, 1], cell[order], key[order]
    counts = np.bincount(key, minlength=ncell * ncell)
    starts = np.cumsum(counts) - counts
    ids = np.arange(n)
    us, vs = [], []
    for dx, dy in _HALF_OFFSETS:
        cx, cy = cell[:, 0] + dx, cell[:, 1] + dy
        ok = (cx >= 0) & (cx < ncell) & (cy >= 0) & (cy < ncell)
        nb = np.where(ok, cx * ncell + cy, 0)
        cnt = np.where(ok, counts[nb], 0)
        # every point against every point of its neighbour cell
        src = np.repeat(ids, cnt)
        first = np.repeat(np.cumsum(cnt) - cnt, cnt)
        cand = np.repeat(starts[nb], cnt) + (np.arange(len(src)) - first)
        ex, ey = x[src] - x[cand], y[src] - y[cand]
        hit = ex * ex + ey * ey <= r * r
        if dx == 0 and dy == 0:
            hit &= cand > src
        us.append(src[hit])
        vs.append(cand[hit])
    a = np.concatenate(us)
    b = np.concatenate(vs)
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    order = np.argsort(lo * n + hi)
    u, v = lo[order].astype(np.int32), hi[order].astype(np.int32)
    return (u, v, assign_weights(len(u), structure_seed), n), cell, ncell


def rgg2d(n: int, avg_degree: float, structure_seed: int,
          seed: int) -> Edges:
    """The structure under the seed's cell-ordered labels: cells in the
    order one of the square's eight symmetries gives, the points of a
    cell shuffled."""
    (u, v, w, n), cell, ncell = structure(n, avg_degree, structure_seed)
    rng = np.random.default_rng(seed)
    sym = int(rng.integers(8))
    cx, cy = cell[:, 0], cell[:, 1]
    if sym & 1:
        cx = ncell - 1 - cx
    if sym & 2:
        cy = ncell - 1 - cy
    if sym & 4:
        cx, cy = cy, cx
    order = np.lexsort((rng.random(n), cx * ncell + cy))
    perm = np.empty(n, np.int64)
    perm[order] = np.arange(n)
    return relabel(u, v, w, perm)


def generate(cfg: dict, seed: int) -> Edges:
    return rgg2d(1 << cfg["log2_n"], cfg["avg_degree"],
                 cfg["structure_seed"], seed)


def edge_bound(cfg: dict) -> int:
    """Every seed relabels the one structure: its edge count."""
    (u, _, _, _), _, _ = structure(1 << cfg["log2_n"], cfg["avg_degree"],
                                   cfg["structure_seed"])
    return len(u)
