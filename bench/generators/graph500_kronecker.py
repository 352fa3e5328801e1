"""Graph500 Kronecker graphs (``"generator": "graph500_kronecker"``).

The edges are drawn on the device, one vectorised initiator draw per
bit, and self-loops and duplicates go with one sort of the ``(u, v)``
pairs.  The run's labelling is a uniformly random vertex permutation,
as the Graph500 specification's.  Configuration keys: ``scale``,
``edgefactor``, ``initiator_a``, ``initiator_b``, ``initiator_c``,
``structure_seed``.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import Edges, assign_weights, jax_key, relabel


def _program(scale: int, edges: int, a: float, b: float, c: float):
    import jax
    import jax.numpy as jnp

    thresholds = (a, a + b, a + b + c)

    @jax.jit
    def draw(key):
        def one_bit(bit, uv):
            r = jax.random.uniform(jax.random.fold_in(key, bit), (edges,))
            quad = sum((r >= t).astype(jnp.int32) for t in thresholds)
            return uv[0] | ((quad >> 1) << bit), uv[1] | ((quad & 1) << bit)

        zero = jnp.zeros((edges,), jnp.int32)
        u, v = jax.lax.fori_loop(0, scale, one_bit, (zero, zero))
        lo, hi = jnp.minimum(u, v), jnp.maximum(u, v)
        lo, hi = jax.lax.sort((lo, hi), num_keys=2)
        keep = lo != hi
        keep = keep.at[1:].set(keep[1:] & ((lo[1:] != lo[:-1])
                                           | (hi[1:] != hi[:-1])))
        return lo, hi, keep

    return draw


def structure(scale: int, edgefactor: int, structure_seed: int,
              a: float = 0.57, b: float = 0.19, c: float = 0.19) -> Edges:
    """Graph500 Kronecker edges, ``edgefactor << scale`` drawn, before
    the vertex permutation."""
    draw = _program(scale, edgefactor << scale, a, b, c)
    lo, hi, keep = (np.asarray(x) for x in draw(jax_key(structure_seed)))
    u, v = lo[keep], hi[keep]
    return u, v, assign_weights(len(u), structure_seed), 1 << scale


def kronecker(scale: int, edgefactor: int, structure_seed: int, seed: int,
              a: float = 0.57, b: float = 0.19, c: float = 0.19) -> Edges:
    """The structure under the seed's uniformly random vertex labels."""
    u, v, w, n = structure(scale, edgefactor, structure_seed, a, b, c)
    return relabel(u, v, w, np.random.default_rng(seed).permutation(n))


def generate(cfg: dict, seed: int) -> Edges:
    return kronecker(cfg["scale"], cfg["edgefactor"], cfg["structure_seed"],
                     seed, cfg["initiator_a"], cfg["initiator_b"],
                     cfg["initiator_c"])


def edge_bound(cfg: dict) -> int:
    """``edgefactor << scale`` edges are drawn, so no seed has more."""
    return cfg["edgefactor"] << cfg["scale"]
