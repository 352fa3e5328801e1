"""Fully-jittable Borůvka MSF with dense component labels.

This is the workhorse shared by every engine in the framework:

* the single-device reference algorithm,
* the per-bucket base case of Filter-Borůvka (Section V of the paper),
* the replicated-vertex base case of the distributed algorithm
  (Section IV-D, Adler et al.), where the per-vertex min-edge reduction
  becomes a cross-device ``allReduce(min)`` over dense vertex vectors,
* the local-preprocessing contraction (Section IV-A) via the
  ``contractible`` restriction hook.

Design notes (TPU adaptation):
  The paper's pointer-doubling exchanges request/reply messages between
  PEs.  On a TPU mesh the natural representation of the vertex->component
  mapping is a dense vector indexed by vertex id (exactly the paper's own
  base-case representation), on which pointer doubling is ``labels =
  labels[labels]`` — a gather that XLA turns into the appropriate
  collective when the vector is sharded.  All shapes are static; padding
  edges carry weight +inf and never win a min-reduction.

Tie-breaking: the effective weight order is lexicographic ``(w, edge_id)``
which is a total order, so the chosen edge set is cycle-free and the MSF
is unique.  This matches the oracle in ``core/oracle.py``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat, obs
from repro.core.graph import EdgeList


class BoruvkaState(NamedTuple):
    labels: jax.Array    # int32 [n] vertex -> component representative
    mst: jax.Array       # bool  [m] chosen MSF edges
    changed: jax.Array   # bool  []  did the last round contract anything
    rounds: jax.Array    # int32 []  rounds executed
    live: jax.Array      # int32 []  alive edge slots summed over rounds
    steps: jax.Array     # int32 []  pointer-doubling passes over rounds


def _doubling_iters(n: int) -> int:
    return max(1, math.ceil(math.log2(max(n, 2))))


def pointer_double(parent: jax.Array, n: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Pointer doubling (Section IV-B / Chung & Condon): ``p = p[p]``
    until a pass changes nothing.

    Returns (roots, steps): the flattened parent array and the passes
    run (int32 []), the last of which found nothing to change.  A forest
    of depth d >= 1 takes ceil(log2 d) + 1 passes; a forest of roots
    alone, one.  The cap of ``_doubling_iters(n)`` passes flattens any
    forest over n ids, and stops a parent array with a longer cycle.
    Inside ``shard_map`` each shard stops on its own array: the loop
    holds no collective.
    """
    cap = _doubling_iters(n)
    axes = compat.vma_of(parent)

    def cond(s):
        _, i, changed = s
        return changed & (i < cap)

    def body(s):
        p, i, _ = s
        q = p[p]
        return q, i + 1, jnp.any(q != p)

    with obs.scope("doubling"):
        roots, steps, _ = jax.lax.while_loop(
            cond, body, (parent, compat.vary(jnp.int32(0), axes),
                         compat.vary(jnp.array(True), axes)))
    return roots, steps


def min_edge_per_component(ru: jax.Array, rv: jax.Array, w: jax.Array,
                           n: int) -> Tuple[jax.Array, jax.Array]:
    """Segmented min-edge reduction (the paper's MINEDGES).

    Args: component labels of both endpoints and weights, for m edges.
    Returns (wmin[n], emin[n]): per-component min incident weight and the
    index of the lexicographically-(w, idx)-smallest achieving edge.
    ``emin == m`` (sentinel) where a component has no alive incident edge.
    """
    wmin, emin, _ = _min_edges(ru, rv, w, n)
    return wmin, emin


@obs.scope("minedges")
def _min_edges(ru: jax.Array, rv: jax.Array, w: jax.Array, n: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``min_edge_per_component`` and the count of its candidate slots
    (alive, finite weight; int32 [])."""
    m = w.shape[0]
    alive = ru != rv
    wk = jnp.where(alive & jnp.isfinite(w), w, jnp.inf)
    wmin = jnp.full((n,), jnp.inf, w.dtype)
    wmin = wmin.at[ru].min(wk)
    wmin = wmin.at[rv].min(wk)
    eidx = jnp.arange(m, dtype=jnp.int32)
    sent = jnp.int32(m)
    cand_u = jnp.where(jnp.isfinite(wk) & (wk == wmin[ru]), eidx, sent)
    cand_v = jnp.where(jnp.isfinite(wk) & (wk == wmin[rv]), eidx, sent)
    emin = jnp.full((n,), sent, jnp.int32)
    emin = emin.at[ru].min(cand_u)
    emin = emin.at[rv].min(cand_v)
    # wk is +inf or finite: counting `wk < inf` keeps XLA from fusing the
    # count with the `isfinite(wk)` tests above and storing that mask
    return wmin, emin, jnp.sum((wk < jnp.inf).astype(jnp.int32))


def contract_components(emin: jax.Array, u: jax.Array, v: jax.Array,
                        labels: jax.Array, n: int,
                        root_mask: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pseudo-tree -> rooted-star contraction by pointer doubling.

    Returns (roots[n], has[n], steps): the new representative of every
    current component label, whether the component chose an edge this
    round, and the doubling passes run (``pointer_double``).
    ``root_mask`` forces components to stay roots (used for shared
    vertices in the distributed algorithm, Section IV-B).
    """
    m = u.shape[0]
    sent = jnp.int32(m)
    with obs.scope("contract"):
        has = emin < sent
        ce = jnp.clip(emin, 0, m - 1)
        cids = jnp.arange(n, dtype=jnp.int32)
        cu = labels[u[ce]]
        cv = labels[v[ce]]
        other = cu + cv - cids  # the endpoint-component that is not `cids`
        parent = jnp.where(has, other, cids)
        if root_mask is not None:
            parent = jnp.where(root_mask, cids, parent)
        # Break 2-cycles: the smaller label of the pair becomes the root.
        gp = parent[parent]
        parent = jnp.where((gp == cids) & (cids < parent), cids, parent)
    roots, steps = pointer_double(parent, n)
    return roots, has, steps


def boruvka_round(u: jax.Array, v: jax.Array, w: jax.Array,
                  labels: jax.Array, mst: jax.Array, n: int,
                  root_mask: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One Borůvka round on dense labels. Returns (labels', mst', changed)."""
    return _boruvka_round_counted(u, v, w, labels, mst, n, root_mask)[:3]


def _boruvka_round_counted(u: jax.Array, v: jax.Array, w: jax.Array,
                           labels: jax.Array, mst: jax.Array, n: int,
                           root_mask: Optional[jax.Array] = None,
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array, jax.Array]:
    """``boruvka_round``, the count of the edge slots MINEDGES could
    choose (endpoints in different components, finite weight) and the
    doubling passes CONTRACT ran (int32 [] each)."""
    m = u.shape[0]
    if m == 0:  # no edge to choose, and nothing to gather the choice from
        return labels, mst, jnp.array(False), jnp.int32(0), jnp.int32(0)
    with obs.scope("label_gather"):
        ru = labels[u]
        rv = labels[v]
    _, emin, live = _min_edges(ru, rv, w, n)
    roots, has, steps = contract_components(emin, u, v, labels, n,
                                            root_mask)
    with obs.scope("contract"):
        ce = jnp.clip(emin, 0, m - 1)
        mst_i = mst.astype(jnp.int32).at[ce].max(has.astype(jnp.int32))
        labels = roots[labels]
    return labels, mst_i.astype(bool), jnp.any(has), live, steps


def run_rounds(u: jax.Array, v: jax.Array, w: jax.Array,
               labels: jax.Array, mst: jax.Array, n: int, max_rounds: int
               ) -> BoruvkaState:
    """Borůvka rounds until none contracts or ``max_rounds`` ran."""
    init = BoruvkaState(labels=labels, mst=mst, changed=jnp.array(True),
                        rounds=jnp.int32(0), live=jnp.int32(0),
                        steps=jnp.int32(0))

    def cond(s: BoruvkaState):
        return s.changed & (s.rounds < max_rounds)

    def body(s: BoruvkaState):
        labels, mst, changed, live, steps = _boruvka_round_counted(
            u, v, w, s.labels, s.mst, n)
        return BoruvkaState(labels, mst, changed, s.rounds + 1,
                            s.live + live, s.steps + steps)

    return jax.lax.while_loop(cond, body, init)


def round_counters(state: BoruvkaState, m: int) -> dict:
    """``obs.record``'s counters of one ``run_rounds`` over m slots."""
    return {"rounds": state.rounds, "live_slots": state.live,
            "slot_rounds": state.rounds * m,
            "doubling_steps": state.steps}


@partial(jax.jit, static_argnames=("n", "max_rounds"))
def boruvka_msf_counted(u: jax.Array, v: jax.Array, w: jax.Array, n: int,
                        max_rounds: Optional[int] = None
                        ) -> Tuple[jax.Array, jax.Array, dict]:
    """``boruvka_msf`` and its ``round_counters``."""
    m = u.shape[0]
    if max_rounds is None:
        # each round at least halves #non-isolated components; a run over
        # k edges touches <= 2k components.
        max_rounds = max(1, math.ceil(math.log2(max(min(n, 2 * m), 2))) + 1)
    final = run_rounds(u, v, w, jnp.arange(n, dtype=jnp.int32),
                       jnp.zeros((m,), bool), n, max_rounds)
    return final.mst, final.labels, round_counters(final, m)


@partial(jax.jit, static_argnames=("n", "max_rounds"))
def boruvka_msf(u: jax.Array, v: jax.Array, w: jax.Array, n: int,
                max_rounds: Optional[int] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Jittable Borůvka. Returns (mst_mask[m] bool, labels[n] int32)."""
    mst, labels, _ = boruvka_msf_counted(u, v, w, n, max_rounds)
    return mst, labels


def boruvka_msf_on(edges: EdgeList, max_rounds: Optional[int] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    return boruvka_msf(edges.u, edges.v, edges.w, edges.n, max_rounds)
