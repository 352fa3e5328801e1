"""What every graph generator of the benchmark shares.

A configuration names its generator, ``bench/generators/<generator>.py``,
which the harness loads by that name.  A generator module gives

* ``generate(cfg, seed) -> (u, v, w, n)``: the configuration's graph,
  labelled by the run's seed;
* ``edge_bound(cfg) -> int``: the most edges any seed's graph has, which
  a cell's pinned slot count has to hold.

Each generator returns canonical undirected edges as host arrays
``(u, v, w, n)``: ``u < v``, no self-loops, no parallel edges, sorted by
``(u, v)``, with float32 weights uniform on [1, 255).  That is the edge
list a user hands the solver, and the edge index is the tie-break of the
exact ``(w, eid)`` forest.

A configuration fixes one graph structure, drawn from its
``structure_seed``; a run's seed draws a labelling of it.  Every seed so
gives the solver the same work (the engines' round counts follow the
structure and the weights, which stay fixed: drawing the structure from
the run's seed moved a solve's time by one round, 8 to 16%, from seed
to seed), while the edge list, its order and the forest's edge indices
are new in every run.

The generators follow ``repro/data/generators.py`` (``rmat``, ``rgg2d``,
``assign_weights``), copied here so that the yardstick does not move
when the program does, and made fast enough to run in every benchmark
run's set-up.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

Edges = Tuple[np.ndarray, np.ndarray, np.ndarray, int]  # u, v, w, n

WEIGHT_LO, WEIGHT_HI = 1.0, 255.0


def assign_weights(m: int, seed: int) -> np.ndarray:
    """Uniform float32 weights on [1, 255), as the paper's Section VII."""
    rng = np.random.default_rng(seed + 0x9E3779B9)
    return rng.uniform(WEIGHT_LO, WEIGHT_HI, size=m).astype(np.float32)


def jax_key(seed: int):
    """A PRNG key from any non-negative integer seed (more than 32 bits)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def relabel(u, v, w, perm: np.ndarray) -> Edges:
    """The edges under ``perm`` (old id -> new id), canonical again."""
    n = len(perm)
    a, b = perm[u], perm[v]
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    order = np.argsort(lo.astype(np.int64) * n + hi)
    return (lo[order].astype(np.int32), hi[order].astype(np.int32),
            w[order], n)
