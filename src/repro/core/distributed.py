"""Distributed Borůvka / Filter-Borůvka over a device mesh (Sections IV+V).

Graph representation (paper Section II-B): both directions of every
undirected edge, lexicographically sorted, 1D-partitioned into equal
padded shards.  Every directed copy carries the undirected edge id
``eid`` so that tie-breaking uses the *direction-independent* total order
``(w, eid)`` — without it, equal-weight edges could be ordered differently
by the two endpoints' components and chosen-edge cycles become possible.

Vertex labels are replicated dense vectors (the representation of the
paper's base case, Adler et al., Section IV-D): the per-round segmented
min-edge reduction then becomes per-shard scatter-min + one
``allReduce(min)`` of an n-vector, and pointer doubling is a local
computation.  This is the *baseline* distribution; the sharded-label
variant with the sparse routed exchange (the paper's scalable path for
n >> memory/PE) lives in ``distributed_sharded.py`` and is documented in
EXPERIMENTS.md §Sharded-label engine (version-portability policy for
both engines: EXPERIMENTS.md §Compat).

Pipeline per the paper's Algorithm 1:
  LOCALPREPROCESSING   -> comm-free contraction of provably-local MST
                          edges (shared boundary vertices stay roots)
  rounds:  MINEDGES    -> scatter-min + pmin      (dense allreduce)
           CONTRACT    -> pointer doubling         (replicated, local)
           EXCHANGE    -> one psum label combine after preprocessing
  filter levels        -> weight-interval buckets from sampled pivots
                          (PIVOTSELECTION), light-to-heavy, Section V
  REDISTRIBUTEMST      -> output mask stays aligned with input slots
"""
from __future__ import annotations

import functools
import math
from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.core.boruvka import _doubling_iters, pointer_double
from repro.core.graph import INVALID_W, CapacityError

# "no chosen edge" sentinel in eid space, shared by every engine (and
# distributed_sharded.py) so the (w, eid) total orders can never diverge.
# Host-side np constant: a jnp scalar would initialize the backend at
# import time and lock the device count.
ESENT = np.int32(2 ** 30)


class CommStats(NamedTuple):
    """Per-solve collective-traffic accounting, shared by both mesh
    engines (ISSUE 2: comm counters are the honest metric on one host).

    ``calls``/``items``/``bytes`` cover the per-round collectives
    (MINEDGES / CONTRACT / EXCHANGELABELS and the preprocessing label
    combine); the two one-off result reductions (weight, count) are
    excluded.  The replicated engine counts its dense allreduces, the
    sharded engine counts its routed all-to-alls — same fields, so
    benchmarks can compare the engines like-for-like.  All are
    device-invariant scalars (out_spec P()).

    ``hits``/``misses``/``pushed`` mirror the sharded engine's
    ghost-label-cache counters (``comm/exchange.py: ExchangeStats`` has
    the field-by-field units; ``misses`` doubles as the routed
    endpoint-lookup item count when the cache is off), and ``injected``
    its fault-injection counter (``comm/faults.py``, ISSUE 7; always 0
    outside an active ``FaultPlan``).  They default to 0 so the
    replicated engine — which has no routed exchanges — keeps
    constructing the 4-field view unchanged.
    """
    calls: jax.Array   # [] int32 — collective invocations
    items: jax.Array   # [] f32 — payload items moved (n-vector: n items)
    bytes: jax.Array   # [] f32 — payload bytes moved
    rounds: jax.Array  # [] int32 — Borůvka rounds executed
    hits: jax.Array = np.float32(0.0)    # [] f32 — ghost-cache hits
    misses: jax.Array = np.float32(0.0)  # [] f32 — routed lookup items
    pushed: jax.Array = np.float32(0.0)  # [] f32 — dirty labels pushed
    injected: jax.Array = np.float32(0.0)  # [] f32 — fault-injected items


class DistGraph(NamedTuple):
    """Shard-major padded directed edge arrays ([p * cap])."""
    u: jax.Array
    v: jax.Array
    w: jax.Array
    eid: jax.Array  # undirected edge id shared by both copies

    @property
    def cap_total(self) -> int:
        return int(self.u.shape[0])


@obs.building()
def build_dist_graph(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int,
                     num_shards: int,
                     cap: Optional[int] = None) -> Tuple[DistGraph, int]:
    """Host-side: canonical undirected edges -> doubled, sorted, padded.

    Returns (graph, cap).  ``eid`` is the index into the *undirected*
    input arrays, so a result mask over slots can be reduced back to the
    input edges via eid.

    ``cap`` pins the per-shard slot count instead of the exact
    ``ceil(2m/p)`` (must be >= it): the serving gateway (ISSUE 6) pads
    every request's capacity up to a shared ladder rung so that
    same-family graphs of slightly different edge counts land on one
    array shape — one ``RoundPlan``, one compiled program.  Padding
    slots carry ``INVALID_W`` like any other tail padding.
    """
    m = len(u)
    with obs.span("build.sort"):
        eid = np.arange(m, dtype=np.int32)
        du = np.concatenate([u, v]).astype(np.int64)
        dv = np.concatenate([v, u]).astype(np.int64)
        dw = np.concatenate([w, w]).astype(np.float32)
        de = np.concatenate([eid, eid])
        order = np.lexsort((dw, dv, du))
        du, dv, dw, de = du[order], dv[order], dw[order], de[order]
    dm = len(du)
    need = max(1, -(-dm // num_shards))
    if cap is None:
        cap = need
    elif cap < need:
        # CapacityError subclasses ValueError, so pre-existing callers
        # catching ValueError (and tests matching "cap") are unaffected
        raise CapacityError(
            f"cap={cap} cannot hold ceil(2m/p)={need} edge slots per "
            f"shard (m={m}, p={num_shards}; "
            f"{dm - cap * num_shards} directed copies would be silently "
            "dropped)", dropped=dm - cap * num_shards)
    with obs.span("build.pack"):
        uu = np.zeros(num_shards * cap, np.int32)
        vv = np.zeros(num_shards * cap, np.int32)
        ww = np.full(num_shards * cap, INVALID_W, np.float32)
        ee = np.zeros(num_shards * cap, np.int32)
        for s in range(num_shards):
            lo, hi = s * cap, min((s + 1) * cap, dm)
            if hi > lo:
                k = hi - lo
                uu[s * cap: s * cap + k] = du[lo:hi]
                vv[s * cap: s * cap + k] = dv[lo:hi]
                ww[s * cap: s * cap + k] = dw[lo:hi]
                ee[s * cap: s * cap + k] = de[lo:hi]
        return DistGraph(jnp.asarray(uu), jnp.asarray(vv), jnp.asarray(ww),
                         jnp.asarray(ee)), cap


# --------------------------------------------------------------------------
# shard-local building blocks (all run inside shard_map)
# --------------------------------------------------------------------------

def shrink_schedule(full: int, floor: int = 1) -> Tuple[int, ...]:
    """Geometric halving ladder ``(full, ceil(full/2), ..., floor)``.

    The shared shrink discipline of the repo: Borůvka at least halves the
    number of active components per round, so any per-round quantity that
    is bounded by the active set can be sized from this ladder.  Used by
    ``_distributed_rounds_shrink`` (the dense engine's per-round vector
    sizes) and by the sharded engine's per-round exchange-capacity
    schedule (``distributed_sharded.py``: the static unroll of decreasing
    MINEDGES / lookup / contract capacities).  For ``full >= 2`` the
    ladder has ``ceil(log2(full)) + 1`` rungs — the same count as the
    engines' round bound ``_doubling_iters(full) + 1``.
    """
    out = [max(int(full), floor)]
    while out[-1] > floor:
        out.append(max(-(-out[-1] // 2), floor))
    return tuple(out)


def quantize_capacity(bound: int, full: int, floor: int = 1) -> int:
    """Smallest ``shrink_schedule(full, floor)`` rung ``>= bound``.

    Snapping measured per-round bounds to the ladder keeps the number of
    distinct (and therefore separately compiled) capacity configurations
    logarithmic while never under-sizing a buffer: the rung is an upper
    bound on ``bound``, and a ``bound`` above every rung returns ``full``
    (callers never pass one, but an explicit undersized user capacity
    must stay undersized so its overflow is *reported*, not papered
    over).
    """
    best = max(int(full), floor)
    for rung in shrink_schedule(full, floor):
        if rung >= bound:
            best = rung
        else:
            break
    return best


def _vary(x, axes):
    """pvary only the axes the value is not already varying over."""
    return compat.vary(x, axes)


def _shared_vertex_root_mask(u: jax.Array, valid: jax.Array, n: int,
                             axes: Tuple[str, ...]) -> jax.Array:
    """Dense [n] mask of shared vertices (edge runs straddling shards).

    A vertex whose edges live on two shards is declared a component root
    (Section IV-B) so that no shard contracts "through" it without
    communication.
    """
    cnt = jnp.sum(valid.astype(jnp.int32))
    has = cnt > 0
    first = jnp.where(has, u[0], -1)
    last = jnp.where(has, u[jnp.clip(cnt - 1, 0, u.shape[0] - 1)], -2)
    # psum of one-hot rows: a [p] gather whose result is replicated
    # (invariant), so callers may return it with a replicated out_spec
    p = compat.axis_size(axes)
    onehot = lax.axis_index(axes) == jnp.arange(p)
    firsts = lax.psum(jnp.where(onehot, first, 0), axes)
    lasts = lax.psum(jnp.where(onehot, last, 0), axes)
    # boundary j|j+1 is shared when shard j's last src == shard j+1's first
    shared = (lasts[:-1] == firsts[1:]) & (lasts[:-1] >= 0)
    shared_ids = jnp.where(shared, lasts[:-1], n)  # n -> dropped
    mask = jnp.zeros((n,), bool).at[shared_ids].set(True, mode="drop")
    return mask, firsts, lasts


def _local_vertex_mask_for_edges(x: jax.Array, firsts, lasts, shard: int,
                                 root_mask_at: jax.Array) -> jax.Array:
    """Is vertex array ``x`` home on this shard and non-shared?"""
    lo = firsts[shard]
    hi = lasts[shard]
    inside = (x >= lo) & (x <= hi) & (lo >= 0)
    return inside & ~root_mask_at


def _local_preprocessing_core(u, v, w, eid, valid, n: int,
                              axes: Tuple[str, ...]):
    """Section IV-A: contract local MST edges without communication.

    Returns this shard's *contribution* (labels[n] deviating from the
    identity only for vertices contracted on this shard — each vertex is
    contracted on at most one shard — and mst_slots[cap] bool).  Callers
    combine contributions their own way: the replicated engine with one
    dense psum(n) (``_local_preprocessing``), the sharded engine with a
    routed label scatter to the owners (distributed_sharded.py), which
    avoids reintroducing the O(n) collective the sharded representation
    exists to avoid.
    """
    cap = u.shape[0]
    shard = lax.axis_index(axes)
    root_mask, firsts, lasts = _shared_vertex_root_mask(u, valid, n, axes)
    local_u = _local_vertex_mask_for_edges(u, firsts, lasts, shard,
                                           root_mask[u])
    local_v = _local_vertex_mask_for_edges(v, firsts, lasts, shard,
                                           root_mask[v])
    local_edge = local_u & local_v & valid

    iota = jnp.arange(n, dtype=jnp.int32)
    sent = jnp.int32(cap)

    def round_(state):
        labels, mst, _, r = state
        ru = labels[u]
        rv = labels[v]
        alive = (ru != rv) & valid
        wk = jnp.where(alive, w, jnp.inf)
        wmin = jnp.full((n,), jnp.inf, w.dtype).at[ru].min(wk).at[rv].min(wk)
        # tie-break by the *global undirected* eid (not the local slot) so
        # the contracted edges are a subset of the unique (w, eid) MSF —
        # the same total order every engine and the oracle use
        esent = ESENT
        at_min_u = jnp.isfinite(wk) & (wk == wmin[ru])
        at_min_v = jnp.isfinite(wk) & (wk == wmin[rv])
        eminid = jnp.full((n,), esent, jnp.int32)
        eminid = eminid.at[ru].min(jnp.where(at_min_u, eid, esent))
        eminid = eminid.at[rv].min(jnp.where(at_min_v, eid, esent))
        slot = jnp.arange(cap, dtype=jnp.int32)
        cu = jnp.where(at_min_u & (eid == eminid[ru]), slot, sent)
        cv = jnp.where(at_min_v & (eid == eminid[rv]), slot, sent)
        emin = jnp.full((n,), sent, jnp.int32).at[ru].min(cu).at[rv].min(cv)
        has = emin < sent
        ce = jnp.clip(emin, 0, cap - 1)
        # contract only if the component's global-min edge is local
        eligible = has & local_edge[ce] & ~root_mask
        emin_m = jnp.where(eligible, emin, sent)
        ce = jnp.clip(emin_m, 0, cap - 1)
        cru = labels[u[ce]]
        crv = labels[v[ce]]
        other = cru + crv - iota
        parent = jnp.where(eligible, other, iota)
        gp = parent[parent]
        parent = jnp.where((gp == iota) & (iota < parent), iota, parent)
        roots, _ = pointer_double(parent, n)
        mst = mst.at[ce].max(eligible.astype(jnp.int32))
        labels = roots[labels]
        return labels, mst, jnp.any(eligible), r + 1

    max_rounds = _doubling_iters(n) + 1

    def cond(state):
        return state[2] & (state[3] < max_rounds)

    labels0 = _vary(iota, axes)
    mst0 = _vary(jnp.zeros((cap,), jnp.int32), axes)
    labels, mst, _, _ = lax.while_loop(
        cond, lambda s: round_(s),
        (labels0, mst0, _vary(jnp.array(True), axes), jnp.int32(0)))
    return labels, mst.astype(bool)


def _local_preprocessing(u, v, w, eid, valid, n: int,
                         axes: Tuple[str, ...]):
    """Replicated combine of the comm-free contraction contributions.

    Returns (labels[n] replicated-consistent, mst_slots[cap] bool).
    One psum(n) label combine at the end (the ghost-label exchange).
    """
    labels, mst = _local_preprocessing_core(u, v, w, eid, valid, n, axes)
    iota = jnp.arange(n, dtype=jnp.int32)
    # EXCHANGELABELS (dense): each vertex is contracted on at most one
    # shard, so summing the deviations from identity merges all shards'
    # label updates in one allreduce.
    labels = lax.psum(labels - iota, axes) + iota
    return labels, mst


def _distributed_rounds(u, v, w, eid, valid, labels, mst, n: int,
                        axes: Tuple[str, ...], active: Optional[jax.Array],
                        max_rounds: int):
    """Borůvka rounds with replicated labels (Sections IV-B..IV-D).

    ``active`` optionally restricts the edge set (the filter levels).
    Chosen-edge marking uses the canonical (u < v) directed copy so each
    undirected MSF edge is marked exactly once across all shards.
    """
    cap = u.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    esent = ESENT

    live = valid if active is None else (valid & active)

    def round_(state):
        labels, mst, _, r = state
        ru = labels[u]
        rv = labels[v]
        alive = (ru != rv) & live
        wk = jnp.where(alive, w, jnp.inf)
        # MINEDGES: per-shard scatter-min + allreduce-min over n-vectors
        wmin_l = jnp.full((n,), jnp.inf, w.dtype).at[ru].min(wk).at[rv].min(wk)
        wmin = lax.pmin(wmin_l, axes)
        cu = jnp.where(jnp.isfinite(wk) & (wk == wmin[ru]), eid, esent)
        cv = jnp.where(jnp.isfinite(wk) & (wk == wmin[rv]), eid, esent)
        emin_l = jnp.full((n,), esent, jnp.int32).at[ru].min(cu).at[rv].min(cv)
        emin = lax.pmin(emin_l, axes)
        has = emin < esent
        # the winning (w, eid) slot(s) on this shard
        win_u = alive & (wk == wmin[ru]) & (eid == emin[ru])
        win_v = alive & (wk == wmin[rv]) & (eid == emin[rv])
        win = win_u | win_v
        # other-endpoint component of each component's chosen edge
        oth_l = jnp.full((n,), -1, jnp.int32)
        oth_l = oth_l.at[ru].max(jnp.where(win_u, rv, -1))
        oth_l = oth_l.at[rv].max(jnp.where(win_v, ru, -1))
        other = lax.pmax(oth_l, axes)
        # CONTRACTCOMPONENTS: replicated pointer doubling
        parent = jnp.where(has & (other >= 0), other, iota)
        gp = parent[parent]
        parent = jnp.where((gp == iota) & (iota < parent), iota, parent)
        roots, _ = pointer_double(parent, n)
        # mark the canonical directed copy exactly once
        mst = mst | (win & (u < v))
        labels = roots[labels]
        return labels, mst, jnp.any(has), r + 1

    def cond(state):
        return state[2] & (state[3] < max_rounds)

    labels, mst, _, r = lax.while_loop(
        cond, round_, (labels, _vary(mst, axes), jnp.array(True),
                       jnp.int32(0)))
    return labels, mst, r


def _weight_pivots(w, valid, num_levels: int, axes: Tuple[str, ...]):
    """PIVOTSELECTION (Section V): global weight quantiles from a sample."""
    cap = w.shape[0]
    s = min(64, cap)
    idx = (jnp.arange(s) * cap) // s
    samp = jnp.where(valid[idx], w[idx], jnp.inf)
    all_samp = jnp.sort(lax.all_gather(samp, axes, tiled=False).reshape(-1))
    nfin = jnp.maximum(jnp.sum(jnp.isfinite(all_samp).astype(jnp.int32)), 1)
    pos = (jnp.arange(1, num_levels) * nfin) // num_levels
    return all_samp[pos]  # [num_levels - 1] ascending pivots


def _distributed_rounds_shrink(u, v, w, eid, valid, labels, mst, n: int,
                               axes: Tuple[str, ...],
                               src_only: bool = False):
    """Beyond-paper §Perf variant: geometrically shrinking dense rounds.

    The replicated-label formulation allReduces O(n)-vectors every round
    => O(n log n) collective volume.  But Borůvka guarantees the number
    of *active* components at round r is <= n / 2^r: a component either
    has no alive edge (done forever — all incident edges internal) or it
    merges.  This variant renumbers the active components into a dense
    prefix after every round (purely local prefix-sum) and allReduces
    arrays of size n/2^r — total volume sum_r n/2^r = 2n, a log2(n)-fold
    reduction of the dominant collective term on large graphs.

    Rounds are Python-unrolled (log2(n)+1), each with static shapes.
    """
    cap = u.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    esent = ESENT
    # per-round vector sizes come from the shared geometric ladder (the
    # halving structure the sharded engine's capacity schedule reuses);
    # for n >= 2 its length equals the old _doubling_iters(n) + 1 round
    # bound.  max(n, 1) — not 2 — so a single-vertex graph's first rung
    # never exceeds the n-sized rep/cid buffers below.
    sizes = shrink_schedule(max(n, 1))
    rounds = len(sizes)

    # active-slot mapping over vertex-label space; initially every vertex
    # label is its own active slot.
    cid = iota  # [n] vertex-label -> active slot (or >= s below)
    rep = iota  # [n-sized buffer] slot -> representative vertex label
    acc_items = 0  # static: allreduced items (3 (s+1)-vectors per round)

    for r, s in enumerate(sizes):
        acc_items += 3 * (s + 1)
        s_next = sizes[r + 1] if r + 1 < rounds else 1
        pad = jnp.int32(s)  # inactive sentinel slot
        ru = jnp.where(valid, cid[labels[u]], pad)
        rv = jnp.where(valid, cid[labels[v]], pad)
        alive = (ru != rv) & valid & (ru < s) & (rv < s)
        wk = jnp.where(alive, w, jnp.inf)
        wmin_l = jnp.full((s + 1,), jnp.inf, w.dtype)
        if src_only:
            # directed both-copy representation: every component sees all
            # of its incident edges as ru somewhere globally, so the
            # rv-side scatters are redundant (§Perf: halves scatter work)
            wmin_l = wmin_l.at[ru].min(wk)
        else:
            wmin_l = wmin_l.at[ru].min(wk).at[rv].min(wk)
        wmin = lax.pmin(wmin_l, axes)
        cu = jnp.where(jnp.isfinite(wk) & (wk == wmin[ru]), eid, esent)
        emin_l = jnp.full((s + 1,), esent, jnp.int32)
        if src_only:
            emin_l = emin_l.at[ru].min(cu)
        else:
            cv = jnp.where(jnp.isfinite(wk) & (wk == wmin[rv]), eid, esent)
            emin_l = emin_l.at[ru].min(cu).at[rv].min(cv)
        emin = lax.pmin(emin_l, axes)
        has = emin[:s] < esent
        win_u = alive & (wk == wmin[ru]) & (eid == emin[ru])
        win_v = alive & (wk == wmin[rv]) & (eid == emin[rv])
        oth_l = jnp.full((s + 1,), -1, jnp.int32)
        if src_only:
            oth_l = oth_l.at[ru].max(jnp.where(win_u, rv, -1))
        else:
            oth_l = oth_l.at[ru].max(jnp.where(win_u, rv, -1))
            oth_l = oth_l.at[rv].max(jnp.where(win_v, ru, -1))
        other = lax.pmax(oth_l, axes)[:s]
        # contraction in slot space (replicated, local)
        sid = jnp.arange(s, dtype=jnp.int32)
        parent = jnp.where(has & (other >= 0), other, sid)
        gp = parent[parent]
        parent = jnp.where((gp == sid) & (sid < parent), sid, parent)
        roots, _ = pointer_double(parent, s)
        mst = mst | ((win_u | win_v) & (u < v))
        # labels: active vertices point at the root slot's representative
        lab_slot = cid[labels]                     # [n]
        act = lab_slot < s
        root_slot = roots[jnp.clip(lab_slot, 0, s - 1)]
        labels = jnp.where(act, rep[root_slot], labels)
        # renumber merged components into [0, s_next)
        merged_root = has[jnp.arange(s)] & (roots == sid)
        # a root slot that merged this round stays active next round
        newid = jnp.cumsum(merged_root.astype(jnp.int32)) - 1
        newid = jnp.where(merged_root, newid, s_next)
        newid = jnp.minimum(newid, s_next)         # overflow-safe clamp
        # map: vertex-label -> next-round slot
        cid_next = jnp.full((n,), jnp.int32(s_next))
        cid_next = cid_next.at[rep[:s]].min(
            jnp.where(merged_root, newid, s_next), mode="drop")
        rep_next = jnp.zeros((n,), jnp.int32)
        rep_next = rep_next.at[jnp.clip(newid, 0, s_next - 1)].max(
            jnp.where(merged_root, rep[:s], 0), mode="drop")
        cid = cid_next
        rep = rep_next
    return labels, mst, rounds, acc_items


# --------------------------------------------------------------------------
# the full per-shard program + host wrapper
# --------------------------------------------------------------------------

def _msf_shard_fn(u, v, w, eid, n: int, axes: Tuple[str, ...],
                  algorithm: str, local_preprocessing: bool,
                  num_levels: int, max_rounds: Optional[int]):
    valid = jnp.isfinite(w)
    iota = jnp.arange(n, dtype=jnp.int32)
    mr = max_rounds or (math.ceil(math.log2(max(n, 2))) + 1)
    p = 1
    for a in axes:
        p *= compat.axis_size(a)
    # analytic-but-threaded collective accounting (CommStats): the dense
    # engine's traffic is fully determined by (n, rounds) — 3 allreduced
    # n-vectors per round (wmin f32, emin i32, other i32)
    calls = jnp.int32(0)
    items = jnp.float32(0.0)
    nbytes = jnp.float32(0.0)
    rounds = jnp.int32(0)

    if local_preprocessing:
        labels, pre_mst = _local_preprocessing(u, v, w, eid, valid, n, axes)
        # psum(n) label combine + the 2 tiny firsts/lasts all_gathers
        calls += 3
        items += jnp.float32(n + 2 * p)
        nbytes += jnp.float32(4 * (n + 2 * p))
    else:
        labels, pre_mst = iota, jnp.zeros(u.shape, bool)

    mst = jnp.zeros(u.shape, bool)
    if algorithm == "boruvka":
        labels, mst, r = _distributed_rounds(u, v, w, eid, valid, labels,
                                             mst, n, axes, None, mr)
        rounds += r
        calls += 3 * r
        items += 3.0 * n * r.astype(jnp.float32)
        nbytes += 12.0 * n * r.astype(jnp.float32)
    elif algorithm in ("boruvka_shrink", "boruvka_shrink_srconly"):
        mst = _vary(mst, axes)
        labels, mst, r, acc = _distributed_rounds_shrink(
            u, v, w, eid, valid, labels, mst, n, axes,
            src_only=algorithm.endswith("srconly"))
        rounds += r
        calls += 3 * r
        items += jnp.float32(acc)
        nbytes += jnp.float32(4 * acc)
    elif algorithm == "filter_boruvka":
        pivots = _weight_pivots(w, valid, num_levels, axes)
        calls += 1
        items += jnp.float32(64 * p)
        nbytes += jnp.float32(4 * 64 * p)
        lo = jnp.float32(-jnp.inf)
        for lvl in range(num_levels):
            hi = pivots[lvl] if lvl < num_levels - 1 else jnp.float32(jnp.inf)
            active = (w > lo) & (w <= hi)
            labels, mst, r = _distributed_rounds(u, v, w, eid, valid, labels,
                                                 mst, n, axes, active, mr)
            rounds += r
            calls += 3 * r
            items += 3.0 * n * r.astype(jnp.float32)
            nbytes += 12.0 * n * r.astype(jnp.float32)
            lo = hi
    else:
        raise ValueError(algorithm)

    # local-preprocessing MST edges were marked per chosen slot; distributed
    # rounds mark canonical copies.  Both mark each undirected edge once.
    full_mask = mst | pre_mst
    weight = lax.psum(jnp.sum(jnp.where(full_mask, w, 0.0)), axes)
    count = lax.psum(jnp.sum(full_mask.astype(jnp.int32)), axes)
    stats = CommStats(calls, items, nbytes, rounds)
    return full_mask, weight, count, labels, stats


@functools.lru_cache(maxsize=64)
def _build_msf_fn(n: int, mesh: jax.sharding.Mesh, axes: Tuple[str, ...],
                  algorithm: str, local_preprocessing: bool,
                  num_levels: int, max_rounds: Optional[int]):
    fn = partial(_msf_shard_fn, n=n, axes=axes, algorithm=algorithm,
                 local_preprocessing=local_preprocessing,
                 num_levels=num_levels, max_rounds=max_rounds)
    spec = P(axes)
    return jax.jit(compat.shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, P(), P(), P(), P())))


def distributed_msf(graph: DistGraph, n: int, mesh: jax.sharding.Mesh,
                    *, algorithm: str = "boruvka",
                    axis_names: Optional[Sequence[str]] = None,
                    local_preprocessing: bool = True,
                    num_levels: int = 4,
                    max_rounds: Optional[int] = None):
    """Run the distributed MSF on a mesh.

    Returns (mask, weight, count, labels, stats): ``mask`` is aligned
    with ``graph`` slots (one canonical directed copy per MSF edge
    marked); ``stats`` is a ``CommStats`` of the per-round collective
    traffic.  The jitted program is cached per (n, mesh, options) so
    repeated solves only pay tracing once.
    """
    axes = tuple(axis_names or mesh.axis_names)
    shard_fn = _build_msf_fn(n, mesh, axes, algorithm, local_preprocessing,
                             num_levels, max_rounds)
    return shard_fn(graph.u, graph.v, graph.w, graph.eid)


def make_mst_step(n: int, cap_total: int, mesh: jax.sharding.Mesh,
                  algorithm: str = "boruvka", **kw):
    """AOT-lowerable distributed MSF step for the dry-run/roofline harness."""
    def step(u, v, w, eid):
        g = DistGraph(u, v, w, eid)
        return distributed_msf(g, n, mesh, algorithm=algorithm, **kw)

    specs = (
        jax.ShapeDtypeStruct((cap_total,), jnp.int32),
        jax.ShapeDtypeStruct((cap_total,), jnp.int32),
        jax.ShapeDtypeStruct((cap_total,), jnp.float32),
        jax.ShapeDtypeStruct((cap_total,), jnp.int32),
    )
    return step, specs
