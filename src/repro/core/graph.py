"""Distributed graph representation: padded edge lists, 1D partition.

The paper represents the graph as a lexicographically sorted sequence of
directed edges, 1D-partitioned over PEs.  We mirror that:

* ``EdgeList`` — a padded struct-of-arrays (u, v, w).  Invalid (padding)
  slots carry ``w == +inf`` and ``u == v == 0`` so they behave as
  infinitely heavy self-loops and are ignored by every algorithm.
* ``partition_edges`` — equal-size 1D split of the sorted directed edge
  sequence (the paper's input format; "shared vertices" arise when a
  vertex's edge run straddles a shard boundary).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

INVALID_W = np.float32(np.inf)


class CapacityError(ValueError):
    """A fixed-capacity edge layout cannot hold the given edges.

    Raised loudly (ISSUE 7) wherever a ``cap``/``pad_to`` argument used
    to be silently trusted: dropping edges past capacity would produce a
    *wrong MSF with no signal*, the exact failure mode the exchange
    layer's overflow accounting exists to prevent.  ``dropped`` is the
    number of edges the requested capacity cannot hold; the serving
    gateway maps this to a typed admission rejection.
    """

    def __init__(self, message: str, dropped: int = 0):
        super().__init__(message)
        self.dropped = int(dropped)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EdgeList:
    """Padded edge list. ``n`` is static (aux) metadata."""

    u: jax.Array  # int32 [m]
    v: jax.Array  # int32 [m]
    w: jax.Array  # float32 [m]; +inf marks padding
    n: int  # number of vertices (static)

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return (self.u, self.v, self.w), self.n

    @classmethod
    def tree_unflatten(cls, n, arrays):
        u, v, w = arrays
        return cls(u=u, v=v, w=w, n=n)

    # -- helpers ----------------------------------------------------------
    @property
    def m(self) -> int:
        return int(self.u.shape[0])

    @property
    def valid(self) -> jax.Array:
        return jnp.isfinite(self.w)

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32))


@obs.building()
def from_numpy(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int,
               pad_to: int | None = None) -> EdgeList:
    """Build a (optionally padded) EdgeList from host arrays.

    ``pad_to`` must hold every edge — a short capacity raises a
    ``CapacityError`` with the dropped count instead of silently
    truncating (ISSUE 7: lost edges are a wrong MSF with no signal).
    """
    m = len(u)
    cap = m if pad_to is None else int(pad_to)
    if cap < m:
        raise CapacityError(
            f"pad_to={cap} cannot hold {m} edges ({m - cap} would be "
            "silently dropped)", dropped=m - cap)
    with obs.span("pack"):
        uu = np.zeros(cap, np.int32)
        vv = np.zeros(cap, np.int32)
        ww = np.full(cap, INVALID_W, np.float32)
        uu[:m] = u
        vv[:m] = v
        ww[:m] = w
        return EdgeList(jnp.asarray(uu), jnp.asarray(vv), jnp.asarray(ww),
                        int(n))


def canonicalize_undirected(u: np.ndarray, v: np.ndarray, w: np.ndarray
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Keep one canonical direction (u < v); drop self-loops."""
    lo = np.minimum(u, v)
    hi = np.maximum(u, v)
    keep = lo != hi
    return lo[keep].astype(np.int32), hi[keep].astype(np.int32), w[keep].astype(np.float32)


def dedup_parallel(u: np.ndarray, v: np.ndarray, w: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Keep the lightest among parallel edges (host-side preprocessing)."""
    order = np.lexsort((w, v, u))
    u, v, w = u[order], v[order], w[order]
    first = np.ones(len(u), bool)
    if len(u) > 1:
        first[1:] = (u[1:] != u[:-1]) | (v[1:] != v[:-1])
    return u[first], v[first], w[first]


def to_directed_sorted(u: np.ndarray, v: np.ndarray, w: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Both directions of every undirected edge, lexicographically sorted.

    This is the paper's on-PE input format (Section II-B).
    """
    du = np.concatenate([u, v])
    dv = np.concatenate([v, u])
    dw = np.concatenate([w, w])
    order = np.lexsort((dw, dv, du))
    return du[order].astype(np.int32), dv[order].astype(np.int32), dw[order].astype(np.float32)


def partition_edges(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int,
                    num_shards: int, cap: int | None = None) -> EdgeList:
    """1D-partition a sorted directed edge list into equal padded shards.

    Returns an EdgeList whose arrays have shape [num_shards * cap] laid out
    shard-major, ready to feed a shard_map over a 1D mesh axis.

    ``cap`` optionally pins the per-shard slot count (capacity-ladder
    callers); it must hold ``ceil(m / num_shards)`` — a short pin raises
    ``CapacityError`` with the dropped count instead of truncating.
    """
    m = len(u)
    need = -(-m // num_shards)  # ceil
    if cap is None:
        cap = need
    elif cap < need:
        raise CapacityError(
            f"cap={cap} cannot hold ceil(m/p)={need} edge slots per "
            f"shard (m={m}, p={num_shards}; "
            f"{m - cap * num_shards} edges would be silently dropped)",
            dropped=m - cap * num_shards)
    uu = np.zeros(num_shards * cap, np.int32)
    vv = np.zeros(num_shards * cap, np.int32)
    ww = np.full(num_shards * cap, INVALID_W, np.float32)
    for s in range(num_shards):
        lo, hi = s * cap, min((s + 1) * cap, m)
        if hi > lo:
            uu[s * cap: s * cap + (hi - lo)] = u[lo:hi]
            vv[s * cap: s * cap + (hi - lo)] = v[lo:hi]
            ww[s * cap: s * cap + (hi - lo)] = w[lo:hi]
    return EdgeList(jnp.asarray(uu), jnp.asarray(vv), jnp.asarray(ww), int(n))


def forest_weight(edges: EdgeList, mask: jax.Array) -> jax.Array:
    """Total weight of the selected (valid) edges."""
    sel = mask & edges.valid
    return jnp.sum(jnp.where(sel, edges.w, 0.0))
