"""Sharded-label distributed Borůvka / Filter-Borůvka (Section IV, the
scalable path for n >> memory/PE).

``core/distributed.py`` replicates the vertex→component label vector on
every shard, which costs O(n) memory per PE and an allReduce of
n-vectors per round — the paper's *base case*.  This module implements
the representation the paper's 65 536-core runs rely on: the label
vector is **1D-sharded by vertex id** (owner of vertex ``vid`` is shard
``vid // vertices_per_shard``) and every label access becomes a routed
message through the capacity-bounded exchange of ``comm/exchange.py``
(the XLA-native stand-in for the paper's sparse ``MPI_Alltoallv``).

The phases, with the communication-minimisation levers of ISSUE 2 (all
individually toggleable; EXPERIMENTS.md §Sharded-label engine records
the measured all-to-all / routed-volume deltas):

  LOCALPREPROCESSING  (``local_preprocessing=True``, Section IV-A)
             Contract provably-local MST edges comm-free, then seed the
             routed rounds with ONE routed label scatter to the owners.
             The contraction runs in the shard's **bucketed vertex
             space** — the distinct source ids of its sorted edge slice,
             at most edges/shard of them — so no [n]-sized scratch is
             ever materialised (ISSUE 3: peak memory O(n/p) in *every*
             phase, not just the carried state).  Edges both of whose
             endpoints were contracted into the same component are
             retired into the ``dead`` mask before the first round.
  MINEDGES   Each edge shard looks up the component of both endpoints
             from the owners (request/reply).  With ``coalesce=True``
             the lexicographically sorted edge array is deduplicated
             first: one request per contiguous equal-endpoint run
             (segmented-scan run detection shared with kernels/segmin),
             answers fanned back out locally — lookup volume drops by
             ~avg-degree and ``lookup_capacity`` shrinks to the
             host-computed run-head bound.  With ``src_only=True`` each
             directed copy ships its ``(comp, w, eid, other)`` candidate
             only to the owner of its *source* component: both directed
             copies exist, so that owner still sees every edge incident
             to its components — 1 routed exchange + 1 confirmation
             instead of 2 + 2.  The owner scatter-mins with the (w, eid)
             order over its owned slots only.
  CONTRACT   Pointer doubling over the sharded parent array: each
             doubling step is one request_reply round asking
             ``owner(parent[x])`` for ``parent[parent[x]]``
             (EXCHANGELABELS).  Slots whose parent is themselves (roots
             and everything without a chosen edge) answer locally and
             never enter the exchange.  The 2-cycle of a pair of
             components that choose each other is broken toward the
             smaller id.  With ``adaptive_doubling=True`` the fixed
             log2(n) schedule becomes a while_loop that stops one step
             after no parent changes (post round 1 contraction trees are
             shallow).
  RELABEL    Every owned vertex re-resolves its label through one more
             lookup of the contracted parent array.  Slots whose
             endpoints resolve to the same component join the persistent
             ``dead`` mask and stop generating requests and candidates.
             With ``relabel_skip=True`` (ISSUE 4) a vertex whose label
             is a component that chose no edge this round is **settled**
             — such a component has no alive incident edge, so neither
             it nor anything merging into it can ever change again (a
             choosing neighbour would have handed it a candidate) — and
             stops requesting for the rest of the level, mirroring
             CONTRACT's self-parent filter; the shrinking driver drops
             the RELABEL capacity below vps accordingly.

Ghost-vertex label cache (ISSUE 4 tentpole, ``ghost_cache=True`` by
default; the paper's ghost vertices, Section IV): the two per-round
endpoint lookups are the dominant routed volume once MINEDGES is
aggregated, and the ``v`` column barely coalesces in slot order (runs of
equal v are short after the lexicographic (u, v) sort).  Two changes:

  * a **v-sorted secondary index** (``VIndex``: a per-shard permutation
    sorting the v column, plus ``kernels/segmin run_metadata`` over the
    permuted view) makes *both* endpoint columns coalesce to one request
    per distinct remote vertex — used by the coalesced lookup path even
    with the cache off;
  * each shard keeps **ghost tables** ``gu``/``gv`` (cached label per
    distinct endpoint value, sized by the host from the distinct-value
    run counts), filled once at setup by live-gated coalesced lookups
    (all-dead runs are never read again, so never filled), after which
    each shard subscribes — one row per **distinct cached component
    root** — with the roots' owners.  Every round the endpoint labels
    are read locally from the tables (cache *hits*), and after the
    contraction each owner multicasts the **root deltas**
    ``(c, parent[c])`` for exactly the merged roots to root ``c``'s
    subscribers (``scatter_updates``, the dirty push); receivers
    rewrite entries by value, and the subscriber bitmasks are forwarded
    to the surviving roots' owners so subscriptions merge along with
    the components.  The dirty set is the merged-root set, which
    shrinks geometrically with the alive-component count — unlike
    per-vertex label churn, which stays flat while a giant component
    absorbs the graph — so steady-state lookup traffic is O(Δroots)
    instead of O(edges/shard) per round.  ``ExchangeStats`` carries
    hit/miss/push counters so the delta is measurable
    (benchmarks/sharded_scaling.py).  The int32 subscriber bitmask caps
    the scheme at 31 shards; larger meshes fall back to coalesced
    lookups automatically.

Shrinking capacity schedule (ISSUE 3 tentpole, ``shrink_capacities``,
default on): with flat capacities every round ships MINEDGES buffers
sized for the worst case ``edge_capacity = edges/shard`` even after the
dead-edge mask has retired most of the graph.  The shrinking driver
instead runs the *same* round body one jitted step at a time from the
host: before each round it bounds next round's exchanges from the
measured dead-edge mask (alive slots per shard for MINEDGES, the
alive-run-head count for coalesced lookups, the alive-component count
per owner for CONTRACT), snaps each bound up to the geometric capacity
ladder shared with ``boruvka_shrink`` (``core/distributed.py:
shrink_schedule`` — a small static unroll of decreasing capacities, so
the number of distinct compiled step programs stays logarithmic), and
compiles/reuses the step at those capacities.  Bounds are exact by
construction — a slot sends at most one candidate, a run sends at most
one request, a component requests at most one parent hop — so overflow
stays 0 and results are bit-identical to the flat engine; the explicit
overflow accounting remains as the safety net for user-supplied
capacities.  The dominant buffer-bytes term thereby decays geometrically
across rounds instead of staying flat (EXPERIMENTS.md §Shrinking
capacity schedule has the measured per-round trajectory).

Plan/execute split (ISSUE 5): the schedule above is also available as
a first-class value.  ``plan_sharded_msf`` runs the host-interleaved
driver once as a *measurement backend* and freezes the capacities it
chose into a serializable ``core/plan.py: RoundPlan``;
``execute_plan`` / ``distributed_sharded_msf(plan=...)`` /
``make_sharded_mst_step(plan=...)`` replay the plan as a
Python-unrolled multi-round program — per-round static capacities, one
compiled artifact, AOT-lowerable — with ``pad(margin)`` headroom for
serving and an overflow/residual → replan fallback that keeps the
never-silent contract.  The dry-run/roofline layer costs a planned
program's compiled memory and collectives without running it.

Chosen-edge marking: in src-only mode a mutual pair of components
necessarily chose the *same* edge (each side's minimum bounds the
other's), and mutuality is exactly the 2-cycle the contraction already
detects — so the owner marks a winner iff it is not the larger side of a
2-cycle, which marks every MSF edge on exactly one directed slot without
the second confirmation exchange.  In the 2-exchange mode the canonical
(u < v) copy is marked, as before.  Either way the slot mask marks each
undirected MSF edge exactly once (the engines' shared contract).

Per-shard label memory is O(n/p) instead of O(n); all exchanges are
capacity-bounded with explicit overflow accounting (never silent): with
the default capacities (``edge_capacity = edges/shard``,
``label_capacity = vertices/shard``, ``lookup_capacity`` = the exact
host-side run-head bound) overflow is impossible and results are exact;
undersized capacities report a positive overflow count and the caller
must retry larger (EXPERIMENTS.md §Sharded-label engine).

Tie-breaking is the direction-independent ``(w, eid)`` order shared by
all engines and the Kruskal oracle, so the produced MSF edge set is
bit-identical across engines (tests/test_engine_equivalence.py).
"""
from __future__ import annotations

import functools
import math
import warnings
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.comm import faults
from repro.comm.exchange import (ExchangeStats, _hops, reply,
                                 routed_exchange, scatter_updates,
                                 scatter_updates_grid)
from repro.core.boruvka import _doubling_iters, pointer_double
from repro.core.distributed import (ESENT, CommStats, DistGraph,
                                    _weight_pivots, quantize_capacity)
from repro.core.msf_checkpoint import CheckpointError, MSFCheckpoint
from repro.core.plan import GhostPlan, RoundPlan, RoundSpec
from repro.kernels.segmin.ops import run_metadata
from repro.kernels.segmin.segmin import owner_scatter_min

# the ghost push encodes subscriber sets as int32 bitmasks; bit 31 is
# the sign bit, so the *flat* push caps at 31 shards.  The two-level
# grid push (ISSUE 10) stores one mask per mesh axis instead — 31 rows
# x 31 columns — lifting the addressable mesh to 961 shards; beyond
# that (or on meshes that do not factor into exactly two axes) the
# engine falls back to coalesced lookups.
MAX_GHOST_SHARDS = 31
MAX_GHOST_SHARDS_GRID = MAX_GHOST_SHARDS ** 2  # 961

# default checkpoint cadence (ISSUE 9): every this-many executed rounds
# both drivers run the verify barrier and snapshot — amortized to keep
# the measured overhead under the 15% acceptance bound at default scale
# (benchmarks/serve_msf.py `recovery` records the number)
DEFAULT_CKPT_EVERY = 8


class VIndex(NamedTuple):
    """Per-shard v-sorted secondary index (ISSUE 4).

    The edge slice is lexicographically (u, v)-sorted, so the v column's
    equal-value runs are short in slot order.  ``perm`` sorts the local
    slots by ``where(valid, v, n)`` (padding keys to the tail), ``runs``
    is ``run_metadata`` over that permuted view (one maximal run per
    distinct v), ``key`` the permuted key column, and ``rank`` maps each
    original slot to its distinct-v rank — the index into the v ghost
    table.  Static per solve: build once, reuse every round.
    """
    perm: jax.Array   # [cap] int32 — local permutation (v-sorted order)
    rank: jax.Array   # [cap] int32 — slot -> distinct-v rank
    runs: Tuple[jax.Array, jax.Array, jax.Array]  # run_metadata(key)
    key: jax.Array    # [cap] int32 — permuted keys (invalid slots = n)


@obs.scope("sort")
def _build_v_index(v: jax.Array, valid: jax.Array, n: int,
                   names: Tuple[str, ...],
                   perm: Optional[jax.Array] = None) -> VIndex:
    """Build the v-sorted index; ``perm`` lets the host-orchestrated
    driver pass its precomputed per-shard argsort (any stable sort of
    the same keys yields identical runs/ranks, so host and device
    constructions are interchangeable)."""
    cap = v.shape[0]
    key0 = jnp.where(valid, v, jnp.int32(n))
    if perm is None:
        perm = jnp.argsort(key0, stable=True).astype(jnp.int32)
    runs = run_metadata(key0, perm=perm)
    rank = compat.vary(jnp.zeros((cap,), jnp.int32), names
                       ).at[perm].set(runs[2])
    return VIndex(perm, rank, runs, key0[perm])


# --------------------------------------------------------------------------
# sharded building blocks (all run inside shard_map)
# --------------------------------------------------------------------------

def _sharded_lookup(table: jax.Array, vids: jax.Array, valid: jax.Array,
                    vps: int, capacity: int, axes: Tuple[str, ...],
                    schedule: str = "grid",
                    stats: Optional[ExchangeStats] = None,
                    count_misses: bool = False,
                    site: str = "lookup"):
    """Resolve ``table[vids[i]]`` where ``table`` is 1D-sharded by id.

    ``table`` is this shard's [vps] slice of a global [p * vps] int32
    array; ``vids`` are global ids.  Owner routing: the request carries
    the id itself, the owner answers ``table[id - base]``, the answer is
    routed back to the requesting slot (the paper's request/reply label
    exchange).  Returns (values [L], ok [L], overflow) — entries with
    ``ok`` False overflowed the exchange and carry garbage; with
    ``stats`` the updated accumulator is appended to the tuple.
    ``count_misses`` books the request items under ``stats.misses`` too
    (endpoint-lookup call sites only — with no ghost cache every
    endpoint lookup is a miss; CONTRACT/RELABEL lookups never count).
    """
    names = tuple(axes)
    if stats is not None:
        return _lookup_request_reply(table, vids, valid, vps, capacity,
                                     names, schedule, stats,
                                     count_misses=count_misses, site=site)
    base = lax.axis_index(names) * vps
    ex = routed_exchange(vids, vids // vps, valid, capacity, names,
                         schedule, site=site)
    off = jnp.clip(ex.recv - base, 0, vps - 1)
    answers = jnp.where(ex.recv_ok, table[off], jnp.int32(-1))
    out = reply(ex, answers, names, schedule)
    return out, ex.sent_ok, ex.overflow


def _lookup_request_reply(table: jax.Array, vids: jax.Array,
                          req: jax.Array, vps: int, capacity: int,
                          names: Tuple[str, ...], schedule: str,
                          stats: ExchangeStats,
                          count_misses: bool = True,
                          site: str = "lookup"):
    """One owner-routed label request/reply leg with the miss accounting
    booked once — the shared core of every lookup/fill variant (only the
    request-set construction and the answer fan-out differ per caller),
    so the ``2 * p * capacity``-slots-per-lookup conservation law of
    ``tests/test_comm.py`` lives in exactly one place.  ``count_misses``
    is False for the CONTRACT/RELABEL lookups, which are not endpoint
    misses.  Returns (out [L] per-request answers, sent_ok [L],
    overflow, stats)."""
    base = lax.axis_index(names) * vps
    items0 = stats.items
    ex = routed_exchange(vids, vids // vps, req, capacity, names,
                         schedule, stats=stats, site=site)
    off = jnp.clip(ex.recv - base, 0, vps - 1)
    answers = jnp.where(ex.recv_ok, table[off], jnp.int32(-1))
    out, st = reply(ex, answers, names, schedule, stats=ex.stats)
    if count_misses:
        st = st._replace(misses=st.misses + (ex.stats.items - items0))
    return out, ex.sent_ok, ex.overflow, st


def _coalesced_lookup(table: jax.Array, vids: jax.Array, runs,
                      valid: jax.Array, vps: int, capacity: int,
                      axes: Tuple[str, ...], schedule: str,
                      stats: ExchangeStats):
    """``_sharded_lookup`` with request coalescing over equal-vid runs.

    ``runs`` is the precomputed ``run_metadata`` over ``vids`` (static
    across rounds): only run heads whose run contains at least one valid
    slot send a request, and the reply fans back out locally through the
    head index.  Divides routed lookup items by the average run length
    and lets ``capacity`` shrink to the run-head bound
    (``default_lookup_capacity``), with the same exact overflow
    accounting — a dropped head drops its whole run, reported through
    ``overflow``/``ok``.  ``runs`` must not be ``None`` — callers
    dispatch to the uncoalesced ``_sharded_lookup`` themselves (see
    ``_round_body``), so the stats accumulator is threaded through
    exactly one path.
    """
    names = tuple(axes)
    head, head_idx, run_id = runs
    any_valid = compat.vary(jnp.zeros(valid.shape, bool), names
                            ).at[run_id].max(valid)
    req = head & any_valid[run_id]
    out_h, ok_h, ovf, st = _lookup_request_reply(
        table, vids, req, vps, capacity, names, schedule, stats)
    return out_h[head_idx], valid & ok_h[head_idx], ovf, st


def _vsorted_lookup(table: jax.Array, vidx: VIndex, valid: jax.Array,
                    vps: int, capacity: int, axes: Tuple[str, ...],
                    schedule: str, stats: ExchangeStats):
    """Coalesced lookup of the v endpoint through the v-sorted index.

    One request per distinct-v run containing a valid slot (the
    run-length win the slot-order v column cannot give); the answers fan
    out per run and back to original slot order through ``vidx.rank``.

    This gathers/scatters through the derived run/rank arrays rather
    than ``vidx.perm`` directly: the run/rank form is what the
    coalesced-reply fan-out needs.
    """
    names = tuple(axes)
    head, head_idx, run_id = vidx.runs
    L = valid.shape[0]
    run_live = compat.vary(jnp.zeros((L,), bool), names
                           ).at[vidx.rank].max(valid)
    req = head & run_live[run_id]
    out_h, ok_h, ovf, st = _lookup_request_reply(
        table, vidx.key, req, vps, capacity, names, schedule, stats)
    idx = jnp.where(head, run_id, L)  # answers live at run heads
    ra = compat.vary(jnp.full((L + 1,), -1, jnp.int32), names
                     ).at[idx].set(out_h, mode="drop")
    okr = compat.vary(jnp.zeros((L + 1,), bool), names
                      ).at[idx].set(ok_h, mode="drop")
    return (ra[vidx.rank], valid & okr[vidx.rank], ovf, st)


# --------------------------------------------------------------------------
# ghost-vertex label cache (ISSUE 4)
# --------------------------------------------------------------------------

def _ghost_fill(table: jax.Array, vids: jax.Array, runs,
                valid: jax.Array, G: int, vps: int, capacity: int,
                axes: Tuple[str, ...], schedule: str,
                stats: ExchangeStats):
    """Fill one ghost table: one coalesced request per distinct-value
    run with >= 1 valid slot (exactly the miss set — booked under
    ``stats.misses``).  Returns (ghost [G] labels by run rank, overflow,
    stats); unrequested/unanswered entries hold -1 and stay unread.
    """
    names = tuple(axes)
    head, head_idx, run_id = runs
    any_valid = compat.vary(jnp.zeros(valid.shape, bool), names
                            ).at[run_id].max(valid)
    req = head & any_valid[run_id]
    out, ok, ovf, st = _lookup_request_reply(
        table, vids, req, vps, capacity, names, schedule, stats,
        site="fill")
    ghost = compat.vary(jnp.full((G,), -1, jnp.int32), names).at[
        jnp.where(ok, run_id, G)].set(out, mode="drop")
    return ghost, ovf, st


def _bit_or_scatter(mask: jax.Array, idx: jax.Array, bits: jax.Array,
                    ok: jax.Array, p: int,
                    names: Tuple[str, ...]) -> jax.Array:
    """``mask[idx[i]] |= bits[i]`` for ok items (drop row = len(mask)).

    jnp scatters have no bitwise-or mode, so the int32 bitmasks are
    expanded to [*, p] bool, combined with a scatter-max per bit, and
    repacked — p <= MAX_GHOST_SHARDS keeps this tiny.
    """
    L = mask.shape[0]
    lanes = jnp.arange(p, dtype=jnp.int32)
    cur = ((mask[:, None] >> lanes) & 1) > 0
    add = (((bits[:, None] >> lanes) & 1) > 0) & ok[:, None]
    pad = compat.vary(jnp.zeros((1, p), bool), names)
    acc = jnp.concatenate([cur, pad]).at[jnp.where(ok, idx, L)].max(add)
    return jnp.sum(acc[:L].astype(jnp.int32) << lanes, axis=1)


@obs.scope("ghost_setup")
def _ghost_setup(u, v, valid, live, lab, vperm, n: int, vps: int,
                 Gu: int, Gv: int, cap_fill_u: int, cap_fill_v: int,
                 cap_sub: int, axes: Tuple[str, ...], schedule: str,
                 stats: ExchangeStats, grid_push: bool = False):
    """Build the per-shard ghost state: tables + root subscriptions.

    Runs once per solve, after preprocessing.  The two coalesced fills
    (one request per distinct live endpoint) are the only vertex-grained
    lookups the ghost engine ever pays; afterwards each shard sends one
    *root subscription* per distinct cached component root — the owners
    accumulate per-owned-root subscriber bitmasks, which the per-round
    delta push keys on.  Everything is gated on ``live`` (``valid``
    minus the preprocessing dead mask, ignoring any filter window): an
    all-dead run can never be read again — the dead mask only grows —
    so filling or subscribing it would only fatten the push.

    Returns (gstate, vidx, runs_u, overflow, stats) with the uniform
    4-tuple ``gstate = (gu, gv, rs_row, rs_col)``.  In flat-push mode
    ``rs_row`` is the single whole-mesh subscriber bitmask and
    ``rs_col`` stays zeros; in grid mode (ISSUE 10) the subscription
    ships the subscriber's *per-axis* bits and the owner accumulates the
    (row mask, col mask) pair whose outer product the two-hop push
    covers.
    """
    names = tuple(axes)
    big = jnp.int32(n)
    runs_u = run_metadata(u)
    vu = jnp.where(valid, u, big)
    vidx = _build_v_index(v, valid, n, names, perm=vperm)
    gu, o1, st = _ghost_fill(lab, vu, runs_u, live, Gu, vps,
                             cap_fill_u, names, schedule, stats)
    gv, o2, st = _ghost_fill(lab, vidx.key, vidx.runs,
                             live[vidx.perm], Gv, vps,
                             cap_fill_v, names, schedule, st)
    # one subscription per distinct cached root: sort the concatenated
    # cached labels (straight-line argsort — outside any loop, see the
    # loop-closure note on _vsorted_lookup) and send the run heads
    p = 1
    for a in names:
        p *= compat.axis_size(a)
    cat = jnp.concatenate([gu, gv])
    cat = jnp.sort(jnp.where(cat >= 0, cat, ESENT))  # unfilled to the pad
    head = jnp.concatenate([compat.vary(jnp.ones((1,), bool), names),
                            cat[1:] != cat[:-1]])
    req = head & (cat < ESENT)
    items0 = st.items
    zeros = compat.vary(jnp.zeros((vps,), jnp.int32), names)
    base = lax.axis_index(names) * vps
    if grid_push:
        row_ax, col_ax = names
        rowbit = jnp.int32(1) << lax.axis_index(row_ax).astype(jnp.int32)
        colbit = jnp.int32(1) << lax.axis_index(col_ax).astype(jnp.int32)
        ex = routed_exchange((cat, jnp.broadcast_to(rowbit, cat.shape),
                              jnp.broadcast_to(colbit, cat.shape)),
                             cat // vps, req, cap_sub, names, schedule,
                             stats=st, site="subscribe")
        st = ex.stats
        st = st._replace(pushed=st.pushed + (st.items - items0))
        rvid = ex.recv[0].reshape(-1) - base
        okr = ex.recv_ok.reshape(-1)
        R = compat.axis_size(row_ax)
        C = compat.axis_size(col_ax)
        rs_row = _bit_or_scatter(zeros, rvid, ex.recv[1].reshape(-1),
                                 okr, R, names)
        rs_col = _bit_or_scatter(zeros, rvid, ex.recv[2].reshape(-1),
                                 okr, C, names)
    else:
        mybit = jnp.int32(1) << lax.axis_index(names).astype(jnp.int32)
        ex = routed_exchange((cat, jnp.broadcast_to(mybit, cat.shape)),
                             cat // vps, req, cap_sub, names, schedule,
                             stats=st, site="subscribe")
        st = ex.stats
        # subscription maintenance rides the push counter so misses +
        # pushed stays the honest total ghost overhead
        st = st._replace(pushed=st.pushed + (st.items - items0))
        rs_row = _bit_or_scatter(zeros, ex.recv[0].reshape(-1) - base,
                                 ex.recv[1].reshape(-1),
                                 ex.recv_ok.reshape(-1), p, names)
        rs_col = zeros
    return ((gu, gv, rs_row, rs_col), vidx, runs_u,
            o1 + o2 + ex.overflow, st)


@obs.scope("push")
def _ghost_push(gstate, parent: jax.Array, vps: int, capacity: int,
                cap_col: int, axes: Tuple[str, ...], schedule: str,
                stats: ExchangeStats, grid_push: bool = False):
    """Root-delta push: invalidate-by-replacement of ghost entries.

    The dirty set is keyed by **component root**, not vertex: a ghost
    entry holds its vertex's current root, and this round's contraction
    rewrote exactly the roots with ``parent[c] != c`` — a set that
    shrinks geometrically with the alive-component count, unlike the
    per-vertex label churn (which stays flat while a giant component
    absorbs the graph).  Each owner multicasts ``(c, parent[c])`` to the
    subscribers of root ``c`` — flat ``scatter_updates``, or the
    two-hop ``scatter_updates_grid`` when ``grid_push`` (the cross
    product of the per-axis masks over-delivers, which is safe exactly
    because receivers rewrite table entries whose *value* is ``c`` via
    one binary search per entry: no entry valued ``c`` → no-op).
    Subscriptions merge along with the components: the owner forwards
    the mask(s) of ``c`` to ``owner(parent[c])``, where they OR into
    the surviving root's mask(s) (``parent`` is fully contracted, so
    forwards always target final roots, never chain).  Overflow follows
    the exchange contract — counted, never silent; a dropped copy would
    leave a stale ghost entry, so results are only trusted at overflow
    0, same as every exchange.
    """
    names = tuple(axes)
    p = 1
    for a in names:
        p *= compat.axis_size(a)
    gu, gv, rs_row, rs_col = gstate
    base = lax.axis_index(names) * vps
    vid = base + jnp.arange(vps, dtype=jnp.int32)
    dirty = (parent != vid) & (rs_row != 0)
    items0 = stats.items
    if grid_push:
        row_ax, col_ax = names
        R = compat.axis_size(row_ax)
        C = compat.axis_size(col_ax)
        upd = scatter_updates_grid((vid, parent), rs_row, rs_col, dirty,
                                   capacity, cap_col, names, stats=stats,
                                   site_row="ghost_push_row",
                                   site_col="ghost_push_col")
        # subscriber masks follow the merge: both axis masks of c move
        # to owner(parent[c]) over the plain routed (request) path
        fx = routed_exchange((parent, rs_row, rs_col), parent // vps,
                             dirty, capacity, names, schedule,
                             stats=upd.stats, site="push")
        st = fx.stats
        st = st._replace(pushed=st.pushed + (st.items - items0))
        rs_row = jnp.where(dirty, 0, rs_row)  # merged c: not a root now
        rs_col = jnp.where(dirty, 0, rs_col)
        fvid = fx.recv[0].reshape(-1) - base
        fok = fx.recv_ok.reshape(-1)
        rs_row = _bit_or_scatter(rs_row, fvid, fx.recv[1].reshape(-1),
                                 fok, R, names)
        rs_col = _bit_or_scatter(rs_col, fvid, fx.recv[2].reshape(-1),
                                 fok, C, names)
    else:
        upd = scatter_updates((vid, parent), rs_row, dirty, capacity,
                              names, schedule, stats=stats, site="push")
        fx = routed_exchange((parent, rs_row), parent // vps, dirty,
                             capacity, names, schedule, stats=upd.stats,
                             site="push")
        st = fx.stats
        st = st._replace(pushed=st.pushed + (st.items - items0))
        rs_row = jnp.where(dirty, 0, rs_row)  # merged c: not a root now
        rs_row = _bit_or_scatter(rs_row,
                                 fx.recv[0].reshape(-1) - base,
                                 fx.recv[1].reshape(-1),
                                 fx.recv_ok.reshape(-1), p, names)
    # apply the received (old root -> new root) pairs by value
    okp = upd.recv_ok.reshape(-1)
    rold = jnp.where(okp, upd.recv[0].reshape(-1), ESENT)
    rnew = upd.recv[1].reshape(-1)
    order = jnp.argsort(rold)  # in-body argsort: loop-safe
    sc = rold[order]
    sr = rnew[order]
    M = sc.shape[0]

    def apply(gt):
        j = jnp.clip(jnp.searchsorted(sc, gt), 0, M - 1)
        hit = sc[j] == gt  # unfilled entries are -1: never match
        return jnp.where(hit, sr[j], gt)

    return ((apply(gu), apply(gv), rs_row, rs_col),
            upd.overflow + fx.overflow, st)


def _relabel_lookup(parent: jax.Array, has: jax.Array, lab: jax.Array,
                    settled: jax.Array, vps: int, capacity: int,
                    axes: Tuple[str, ...], schedule: str,
                    stats: ExchangeStats):
    """RELABEL with the settled-vertex skip (ISSUE 4 satellite).

    Unsettled owned vertices ask ``owner(lab[x])`` for the contracted
    parent *and* whether that component chose an edge this round.  A
    component that chose nothing has no alive incident edge, so no
    neighbour can ever merge into it either (it would have received that
    candidate) — its members' labels are final for the level and stop
    requesting, which is what lets the shrinking driver drop the RELABEL
    capacity below vps (the dense analogue of CONTRACT's self-parent
    filter).  Returns (lab, settled, overflow, stats).
    """
    names = tuple(axes)
    base = lax.axis_index(names) * vps
    req = ~settled
    ex = routed_exchange(lab, lab // vps, req, capacity, names, schedule,
                         stats=stats, site="relabel")
    off = jnp.clip(ex.recv - base, 0, vps - 1)
    ans_lab = jnp.where(ex.recv_ok, parent[off], jnp.int32(-1))
    ans_cho = jnp.where(ex.recv_ok, has[off], False)
    (out_lab, out_cho), st = reply(ex, (ans_lab, ans_cho), names,
                                   schedule, stats=ex.stats)
    okr = req & ex.sent_ok
    lab = jnp.where(okr, out_lab, lab)
    settled = settled | (okr & ~out_cho)
    return lab, settled, ex.overflow, st


def _sharded_preprocess(u, v, w, eid, valid, n: int, vps: int,
                        capacity: int, axes: Tuple[str, ...],
                        schedule: str, stats: ExchangeStats):
    """Sharded LOCALPREPROCESSING (Section IV-A) with O(edges/shard) peak.

    PR 2's version ran the replicated engine's dense contraction core
    and scattered the changed labels to the owners — correct, but its
    transient [n] scratch (per-shard label / min-reduction vectors and
    an L = n routed exchange) made preprocessing the one phase whose
    *peak* memory was O(n) per device.  This version contracts in the
    shard's **bucketed vertex space** instead: the distinct source ids
    of its (lexicographically sorted) edge slice, indexed by run rank —
    at most cap = edges/shard of them.  Every endpoint of a
    provably-local edge appears as a source on this shard (the doubled
    representation guarantees the reverse copy, and a source run that
    straddles a shard boundary makes its vertex shared, hence
    non-local), so run ranks cover every vertex the contraction may
    touch and all scratch is [cap + 1]-sized, never [n].

    The contraction itself is the Section IV-A discipline of
    ``_local_preprocessing_core`` transplanted into rank space: shared
    boundary vertices stay roots, a component contracts only if its
    global (w, eid)-minimum edge is provably local, ties break on the
    global undirected eid, so the contracted edges are a subset of the
    unique MSF and the final edge set stays bit-identical to the
    Kruskal oracle.

    Returns (lab [vps], pre_mst [cap] bool, dead0 [cap] bool, overflow,
    stats, (rounds, live, steps)): this shard's round count, and its
    alive slots and pointer-doubling passes summed over the rounds
    (int32 []).  The owner scatter ships one (vid, root) pair per
    *changed distinct vertex* (L = cap, down from the old L = n): an
    owner owns ``vps`` vertices and a shard has at most cap distinct
    sources, so the effective ``min(capacity, cap)`` stays overflow-free
    by construction for the default ``label_capacity``.
    """
    names = tuple(axes)
    cap = u.shape[0]
    big = jnp.int32(n)  # > every vertex id; doubles as "no vertex"

    # --- shard boundary structure (tiny [p] all_gathers, no [n] mask) --
    with obs.scope("sort"):
        cnt = jnp.sum(valid.astype(jnp.int32))
        has_edges = cnt > 0
        first = jnp.where(has_edges, u[0], -1)
        last = jnp.where(has_edges, u[jnp.clip(cnt - 1, 0, cap - 1)], -2)
        firsts = lax.all_gather(first, names, tiled=False).reshape(-1)
        lasts = lax.all_gather(last, names, tiled=False).reshape(-1)
    p = firsts.shape[0]
    k = max(p - 1, 1)
    with obs.scope("sort"):
        if p > 1:
            shared = (lasts[:-1] == firsts[1:]) & (lasts[:-1] >= 0)
            sh_ids = jnp.sort(jnp.where(shared,
                                        lasts[:-1].astype(jnp.int32), big))
        else:
            sh_ids = compat.vary(jnp.full((k,), big), names)

    def is_shared(x):
        j = jnp.clip(jnp.searchsorted(sh_ids, x), 0, k - 1)
        return sh_ids[j] == x

    # --- bucketed local vertex space: distinct sources by run rank -----
    with obs.scope("sort"):
        vu = jnp.where(valid, u, big)  # valid slots are a sorted prefix
        head = jnp.concatenate([compat.vary(jnp.ones((1,), bool), names),
                                vu[1:] != vu[:-1]])
        du = jnp.cumsum(head.astype(jnp.int32)) - 1      # [cap] slot -> rank
        uvals = compat.vary(jnp.full((cap,), big), names).at[du].set(vu)
        dv = jnp.clip(jnp.searchsorted(uvals, v), 0, cap - 1)
        v_found = (uvals[dv] == v) & valid
        shared_rank = is_shared(uvals)
        local_edge = valid & v_found & ~is_shared(u) & ~is_shared(v)

    iota = jnp.arange(cap, dtype=jnp.int32)
    sent = jnp.int32(cap)  # drop row of the [cap + 1] scatter arrays
    nloc = max(min(n, cap), 2)  # distinct local vertices <= min(n, cap)
    max_rounds = _doubling_iters(nloc) + 1

    def round_(state):
        lab, mst, _, r, live, steps = state
        with obs.scope("label_gather"):
            ru = lab[du]
            rvx = jnp.where(v_found, lab[dv], sent)
            same = v_found & (lab[du] == lab[dv])
        with obs.scope("minedges"):
            alive = valid & ~same
            live = live + jnp.sum(alive.astype(jnp.int32))
            wk = jnp.where(alive, w, jnp.inf)
            wmin = jnp.full((cap + 1,), jnp.inf, w.dtype
                            ).at[ru].min(wk).at[rvx].min(wk)
            # tie-break by the *global undirected* eid (not the local slot
            # or rank) so the contracted edges are a subset of the unique
            # (w, eid) MSF — the same total order every engine uses
            at_min_u = jnp.isfinite(wk) & (wk == wmin[ru])
            at_min_v = jnp.isfinite(wk) & (wk == wmin[rvx])
            eminid = jnp.full((cap + 1,), ESENT, jnp.int32)
            eminid = eminid.at[ru].min(jnp.where(at_min_u, eid, ESENT))
            eminid = eminid.at[rvx].min(jnp.where(at_min_v, eid, ESENT))
            cu = jnp.where(at_min_u & (eid == eminid[ru]), iota, sent)
            cv = jnp.where(at_min_v & (eid == eminid[rvx]), iota, sent)
            emin = jnp.full((cap + 1,), sent, jnp.int32
                            ).at[ru].min(cu).at[rvx].min(cv)
        with obs.scope("contract"):
            has = emin[:cap] < sent
            ce = jnp.clip(emin[:cap], 0, cap - 1)
            # contract only if the component's global-min edge is local
            eligible = has & local_edge[ce] & ~shared_rank
            emin_m = jnp.where(eligible, emin[:cap], sent)
            ce = jnp.clip(emin_m, 0, cap - 1)
            cru = lab[du[ce]]
            crv = lab[dv[ce]]
            other = cru + crv - iota
            parent = jnp.where(eligible, other, iota)
            gp = parent[parent]
            parent = jnp.where((gp == iota) & (iota < parent), iota, parent)
        roots, k = pointer_double(parent, nloc)
        with obs.scope("contract"):
            mst = mst.at[ce].max(eligible.astype(jnp.int32))
            lab = roots[lab]
        return lab, mst, jnp.any(eligible), r + 1, live, steps + k

    def cond(state):
        return state[2] & (state[3] < max_rounds)

    lab0 = compat.vary(iota, names)
    mst0 = compat.vary(jnp.zeros((cap,), jnp.int32), names)
    zero = compat.vary(jnp.int32(0), names)
    lab, mst, _, rounds, live, steps = lax.while_loop(
        cond, round_,
        (lab0, mst0, compat.vary(jnp.array(True), names), jnp.int32(0),
         zero, zero))

    # --- one routed (vid, root) scatter to the owners ------------------
    with obs.scope("exchange"):
        groot = uvals[lab]                 # [rank] -> global root vid
        root_slot = groot[du]              # [cap] per-slot root of its source
        changed = head & valid & (root_slot != u)
        ex = routed_exchange((u, root_slot), u // vps, changed,
                             min(capacity, cap), names, schedule,
                             stats=stats, site="prep")
        base = lax.axis_index(names) * vps
        vid = base + jnp.arange(vps, dtype=jnp.int32)
        rvid = ex.recv[0].reshape(-1)
        rlab = ex.recv[1].reshape(-1)
        ok = ex.recv_ok.reshape(-1)
        off = jnp.where(ok, rvid - base, vps)  # vps = drop row
        lab_out = jnp.concatenate([vid, jnp.full((1,), -1, jnp.int32)]
                                  ).at[off].set(rlab)[:vps]
    with obs.scope("label_gather"):
        same = v_found & (lab[du] == lab[dv])
        dead0 = (u == v) | same  # locally-internal edges incl. self-loops
    return (lab_out, mst.astype(bool), dead0, ex.overflow, ex.stats,
            (rounds, live, steps))


def _owner_scatter_min(comp, wc, ec, oc, okc, base, vps: int,
                       use_pallas: bool = False):
    """Owner-side (w, eid)-ordered scatter-min over owned component slots.

    Shared by both MINEDGES variants so the tie-break discipline cannot
    diverge between them.  ``comp/wc/ec/oc/okc`` are the flat received
    candidates; slot ``vps`` is the drop row for unused buffer entries.
    Returns (has [vps], other [vps], is_win [flat], off [flat]).

    ``use_pallas=True`` (the ``pallas_minedges`` lever, ISSUE 8) routes
    the table build through the fused ``owner_scatter_min`` kernel —
    one grid sweep producing (wmin, emin, other) per owned slot with
    the identical lexicographic order, no ``[vps+1]`` scatter
    intermediates — and keeps only the O(flat) winner-confirmation
    gathers in jnp.  Both branches return bit-identical values (the
    property wall of tests/test_kernels_fuzz.py pins this).
    """
    off = jnp.where(okc, comp - base, vps)
    if use_pallas:
        # garbage buffer rows may hold out-of-range comps: clamp to a
        # real row, the kernel's ok mask drops them before they touch it
        idx = jnp.where(okc, comp - base, 0)
        wt, et, pt, _ = owner_scatter_min(idx, wc, ec, oc, oc, okc, vps)
        wmin = jnp.concatenate([wt.astype(wc.dtype),
                                jnp.full((1,), jnp.inf, wc.dtype)])
        emin = jnp.concatenate([et, jnp.full((1,), ESENT, jnp.int32)])
        at_min = okc & (wc == wmin[off])
        is_win = at_min & (ec == emin[off])
        return et < ESENT, pt, is_win, off
    wmin = jnp.full((vps + 1,), jnp.inf, wc.dtype).at[off].min(
        jnp.where(okc, wc, jnp.inf))
    at_min = okc & (wc == wmin[off])
    emin = jnp.full((vps + 1,), ESENT, jnp.int32).at[off].min(
        jnp.where(at_min, ec, ESENT))
    is_win = at_min & (ec == emin[off])
    other = jnp.full((vps + 1,), -1, jnp.int32).at[off].max(
        jnp.where(is_win, oc, -1))
    has = emin[:vps] < ESENT
    return has, other[:vps], is_win, off


@obs.scope("minedges")
def _sharded_minedges(ru, rv, wk, eid, alive, vps: int, capacity: int,
                      axes: Tuple[str, ...], schedule: str,
                      stats: ExchangeStats, use_pallas: bool = False):
    """Owner-computes MINEDGES, 2-exchange variant (the PR 1 baseline).

    Each *directed* edge copy ships a ``(comp, w, eid, other)`` candidate
    to the owner of both its source component (keyed ``ru``) and its
    destination component (keyed ``rv``): together they hand every owner
    all edges incident to its components.  The owner scatter-mins with
    the (w, eid) order over its [vps] slots and confirms winners back to
    the submitting slot, so the caller can mark the canonical copy.

    Returns (has [vps], other [vps], win [L], overflow, stats).
    """
    names = tuple(axes)
    base = lax.axis_index(names) * vps
    ex_u = routed_exchange((ru, wk, eid, rv), ru // vps, alive, capacity,
                           names, schedule, stats=stats, site="minedges")
    ex_v = routed_exchange((rv, wk, eid, ru), rv // vps, alive, capacity,
                           names, schedule, stats=ex_u.stats,
                           site="minedges")

    def flat(ex):
        comp, w_, e_, o_ = ex.recv
        return (comp.reshape(-1), w_.reshape(-1), e_.reshape(-1),
                o_.reshape(-1), ex.recv_ok.reshape(-1))

    ku, wu, eu, ou, oku = flat(ex_u)
    kv, wv, ev, ov, okv = flat(ex_v)
    comp = jnp.concatenate([ku, kv])
    wc = jnp.concatenate([wu, wv])
    ec = jnp.concatenate([eu, ev])
    oc = jnp.concatenate([ou, ov])
    okc = jnp.concatenate([oku, okv])
    has, other, is_win, _ = _owner_scatter_min(comp, wc, ec, oc, okc,
                                               base, vps, use_pallas)
    # confirm winners to the submitting slots (both exchanges carry the
    # same (w, eid) for the two copies of an undirected edge, so a slot
    # wins iff either of its endpoint components chose it)
    nu = ku.shape[0]
    win_u, st = reply(ex_u, is_win[:nu].reshape(ex_u.recv_ok.shape), names,
                      schedule, stats=ex_v.stats)
    win_v, st = reply(ex_v, is_win[nu:].reshape(ex_v.recv_ok.shape), names,
                      schedule, stats=st)
    win = (win_u & ex_u.sent_ok) | (win_v & ex_v.sent_ok)
    return has, other, win, ex_u.overflow + ex_v.overflow, st


@obs.scope("minedges")
def _sharded_minedges_src(ru, rv, wk, eid, alive, runs, vps: int,
                          capacity: int, axes: Tuple[str, ...],
                          schedule: str, stats: ExchangeStats,
                          use_pallas: bool = False):
    """Owner-computes MINEDGES, src-only variant (ISSUE 2 lever 3 +
    ISSUE 3 per-run candidate aggregation).

    Both directed copies of every edge are present, so the owner of
    component ``c`` already receives every edge incident to ``c``
    through the ``ru``-keyed exchange alone (the invariant
    ``boruvka_shrink_srconly`` exploits in the replicated engine): the
    ``rv``-keyed exchange is dropped, halving MINEDGES to 1 routed
    exchange + 1 confirmation.

    Candidates are additionally **pre-aggregated per source run** (the
    classic combiner): the edge array is sorted by source, every slot of
    a contiguous equal-``u`` run shares its source component, and the
    owner's scatter-min only needs each run's local (w, eid)-argmin —
    min-of-mins is exact and the tie order is unchanged, so the chosen
    edge set is bit-identical.  One candidate per *alive run* instead of
    one per alive slot divides the exchange volume by the average run
    length and — decisive for the shrinking capacity schedule — makes
    the host's exact per-(shard, owner) candidate bound decay with the
    alive-run count rather than the raw alive-edge count
    (``_minedges_capacity_bound``).

    The confirmation is deferred — the caller replies through the
    returned ``ex`` once the contraction's first lookup has revealed
    which winners are the larger side of a 2-cycle (see module
    docstring: exact-once marking), then fans the per-run confirmation
    back onto the run's argmin slot via ``loc_win``/``head_idx``.

    Returns (has [vps], other [vps], is_win [p*C] flat, off [p*C] flat
    owner slot per candidate, ex, loc_win [L] — the run's argmin slot,
    head_idx [L] — each slot's run head).
    """
    names = tuple(axes)
    base = lax.axis_index(names) * vps
    head, head_idx, run_id = runs
    L = ru.shape[0]
    if use_pallas:
        # fused combine (ISSUE 8): one kernel sweep yields the per-run
        # (min w, argmin eid) plus both payload channels — the chosen
        # other-endpoint component (max rv over the run's argmin slots)
        # and the run's own component (ru is constant within an equal-u
        # run, so max-over-alive == ru-at-winner) — without the five
        # scatter intermediates.  Dead runs come back (inf, ESENT, -1,
        # -1) in both paths, and alive => finite wk, so run-aliveness
        # is exactly isfinite(wtbl).
        wtbl, etbl, otbl, ctbl = owner_scatter_min(
            run_id, wk, eid, rv, ru, alive, L)
        wtbl = wtbl.astype(wk.dtype)
        at_min = alive & (wk == wtbl[run_id])
        loc_win = at_min & (eid == etbl[run_id])
        send = head & jnp.isfinite(wtbl)[run_id]
        comp_c = ctbl[run_id]
        payload = (comp_c, wtbl[run_id], etbl[run_id], otbl[run_id])
    else:
        # per-run segmented (w, eid) argmin over alive slots (O(cap)
        # scratch)
        wrun = compat.vary(jnp.full((L,), jnp.inf, wk.dtype), names
                           ).at[run_id].min(wk)
        at_min = alive & (wk == wrun[run_id])
        erun = compat.vary(jnp.full((L,), ESENT, jnp.int32), names
                           ).at[run_id].min(jnp.where(at_min, eid, ESENT))
        loc_win = at_min & (eid == erun[run_id])
        orun = compat.vary(jnp.full((L,), -1, jnp.int32), names
                           ).at[run_id].max(jnp.where(loc_win, rv, -1))
        crun = compat.vary(jnp.full((L,), -1, jnp.int32), names
                           ).at[run_id].max(jnp.where(alive, ru, -1))
        anyrun = compat.vary(jnp.zeros((L,), bool), names
                             ).at[run_id].max(alive)
        send = head & anyrun[run_id]
        comp_c = crun[run_id]
        payload = (comp_c, wrun[run_id], erun[run_id], orun[run_id])
    ex = routed_exchange(payload, comp_c // vps, send, capacity,
                         names, schedule, stats=stats, site="minedges")
    comp, w_, e_, o_ = (x.reshape(-1) for x in ex.recv)
    okc = ex.recv_ok.reshape(-1)
    has, other, is_win, off = _owner_scatter_min(comp, w_, e_, o_, okc,
                                                 base, vps, use_pallas)
    return has, other, is_win, off, ex, loc_win, head_idx


@obs.scope("doubling")
def _sharded_contract(has, other, n: int, vps: int, capacity: int,
                      axes: Tuple[str, ...], schedule: str,
                      adaptive: bool, stats: ExchangeStats):
    """Pointer doubling over the sharded parent array (request/reply).

    Every owned slot is a potential component root: roots with a chosen
    edge point at the other endpoint's component, everything else at
    itself.  The 2-cycle of mutually chosen components keeps the smaller
    id as root; then doubling rounds of one routed lookup each — a fixed
    log2(n) schedule, or (``adaptive``) a while_loop that stops one step
    after a psum reports no parent changed, which post round 1 cuts the
    schedule to the actual tree depth.  The iteration cap stays at
    log2(n) either way, so undersized capacities (garbage answers) can
    not loop forever.

    Self-parents answer locally: only ``parent[x] != x`` rows enter the
    exchange (a root's grandparent is itself), and the requesting set
    only shrinks as doubling converges.  That is what lets the shrinking
    capacity driver bound ``capacity`` by the per-owner alive-component
    count instead of the flat vps — only components with a chosen edge
    ever have a non-self parent.

    Returns (parent [vps] fully contracted, keep [vps] — exact-once
    owner-side marking decision for src-only MINEDGES (winner and not
    the larger side of a 2-cycle), overflow, stats).
    """
    names = tuple(axes)
    base = lax.axis_index(names) * vps
    vid = base + jnp.arange(vps, dtype=jnp.int32)
    parent0 = jnp.where(has, other, vid)

    def hop(par, st):
        req = par != vid
        nxt, _, o, st = _sharded_lookup(par, par, req, vps, capacity,
                                        names, schedule, stats=st,
                                        site="contract")
        return jnp.where(req, nxt, par), o, st

    gp, ov0, stats = hop(parent0, stats)
    # a 2-cycle (mutually chosen components) necessarily chose the SAME
    # edge — each side's minimum bounds the other's — so `keep` marks
    # every winning (component, edge) pair on exactly one owner
    mutual = gp == vid
    keep = has & (~mutual | (vid < parent0))
    parent = jnp.where(mutual & (vid < parent0), vid, parent0)
    iters = _doubling_iters(n)

    if adaptive:
        def dbl_a(carry):
            par, ov, st, i, _ = carry
            nxt, o, st = hop(par, st)
            chg = lax.psum(jnp.sum((nxt != par).astype(jnp.int32)),
                           names) > 0
            return nxt, ov + o, st, i + 1, chg

        def cond(carry):
            return carry[4] & (carry[3] < iters)

        parent, ov, stats, _, _ = lax.while_loop(
            cond, dbl_a,
            (parent, ov0, stats, jnp.int32(0), jnp.array(True)))
    else:
        def dbl(_, carry):
            par, ov, st = carry
            nxt, o, st = hop(par, st)
            return nxt, ov + o, st

        parent, ov, stats = lax.fori_loop(0, iters, dbl,
                                          (parent, ov0, stats))
    return parent, keep, ov, stats


def _round_body(u, v, w, eid, live0, lab, mst, dead, runs_u, runs_v,
                vidx, gstate, settled, n: int, vps: int,
                names: Tuple[str, ...], cap_edge: int, cap_label: int,
                cap_lookup: int, cap_contract: int, cap_push: int,
                cap_push_col: int, schedule: str, coalesce: bool,
                src_only: bool, adaptive: bool, ghost: bool,
                relabel_skip: bool, pallas_minedges: bool,
                grid_push: bool, stats: ExchangeStats):
    """One MINEDGES → CONTRACT → RELABEL round over 1D-sharded labels.

    Shared verbatim by the fused while_loop engine (flat capacities,
    AOT-lowerable) and the host-orchestrated shrinking-capacity driver,
    so the two execution modes cannot diverge semantically — they only
    differ in the static capacities each round is compiled with.
    ``cap_contract`` bounds the doubling lookups; the flat path passes
    ``cap_label`` (vps) for it, the shrinking driver the per-owner
    alive-component bound.

    Endpoint resolution picks one of four paths (same values, different
    routed volume): ``ghost`` reads both labels from the local ghost
    tables (cache hits; coherence maintained by the end-of-round dirty
    push); ``coalesce`` sends one request per equal-vid run — the u
    column in slot order, the v column through the v-sorted index
    (``vidx``) or, when only ``runs_v`` is given, in slot order (the
    PR 3 path, kept reproducible as the ``vsorted_index=False``
    comparator); the fallback (all None) requests per slot.

    Returns (lab, mst, dead, gstate, settled, go, overflow_delta, stats).
    """
    live = live0 & ~dead
    if ghost:
        with obs.scope("label_gather"):
            gu, gv = gstate[0], gstate[1]
            head_u, _, run_id_u = runs_u
            head_v, _, run_id_v = vidx.runs
            au = compat.vary(jnp.zeros(live.shape, bool), names
                             ).at[run_id_u].max(live)
            # rank-keyed (never perm-keyed: see _vsorted_lookup)
            # run-liveness
            av = compat.vary(jnp.zeros(live.shape, bool), names
                             ).at[vidx.rank].max(live)
            hits = lax.psum(
                jnp.sum((head_u & au[run_id_u]).astype(jnp.float32))
                + jnp.sum((head_v & av[run_id_v]).astype(jnp.float32)),
                names)
            st = stats._replace(hits=stats.hits + hits)
            ru = gu[jnp.clip(run_id_u, 0, gu.shape[0] - 1)]
            rv = gv[jnp.clip(vidx.rank, 0, gv.shape[0] - 1)]
            looked = live
            o1 = o2 = jnp.int32(0)
    else:
        # dispatch here, not inside _coalesced_lookup: exactly one of
        # the two paths runs per endpoint, each booking its own slots
        # once (runs_u may exist for src_only even when coalesce is off)
        with obs.scope("lookup"):
            if coalesce and runs_u is not None:
                ru, ok_u, o1, st = _coalesced_lookup(
                    lab, u, runs_u, live, vps, cap_lookup, names, schedule,
                    stats)
            else:
                ru, ok_u, o1, st = _sharded_lookup(
                    lab, u, live, vps, cap_lookup, names, schedule,
                    stats=stats, count_misses=True)
            if coalesce and vidx is not None:
                rv, ok_v, o2, st = _vsorted_lookup(
                    lab, vidx, live, vps, cap_lookup, names, schedule, st)
            elif coalesce and runs_v is not None:
                rv, ok_v, o2, st = _coalesced_lookup(
                    lab, v, runs_v, live, vps, cap_lookup, names, schedule,
                    st)
            else:
                rv, ok_v, o2, st = _sharded_lookup(
                    lab, v, live, vps, cap_lookup, names, schedule,
                    stats=st, count_misses=True)
            looked = ok_u & ok_v
    with obs.scope("minedges"):
        # dead-edge retirement: same component now => same forever
        dead = dead | (looked & (ru == rv))
        alive = looked & (ru != rv) & live
        wk = jnp.where(alive, w, jnp.inf)
    if src_only:
        has, other, is_win, off, ex, loc_win, head_idx = \
            _sharded_minedges_src(ru, rv, wk, eid, alive, runs_u, vps,
                                  cap_edge, names, schedule, st,
                                  pallas_minedges)
        parent, keep, o4, st = _sharded_contract(
            has, other, n, vps, cap_contract, names, schedule, adaptive,
            ex.stats)
        with obs.scope("contract"):
            keep_ext = jnp.concatenate([keep, jnp.zeros((1,), bool)])
            confirm = (is_win & keep_ext[off]).reshape(ex.recv_ok.shape)
            win, st = reply(ex, confirm, names, schedule, stats=st)
            # per-run confirmation fans back onto the run's argmin slot;
            # owner-side dedup => exactly one directed slot per MSF edge
            mst = mst | (loc_win & (win & ex.sent_ok)[head_idx])
        o3 = ex.overflow
    else:
        has, other, win, o3, st = _sharded_minedges(
            ru, rv, wk, eid, alive, vps, cap_edge, names, schedule, st,
            pallas_minedges)
        # both directed copies are confirmed; mark only the canonical
        # one so the global mask is exact-once
        with obs.scope("contract"):
            mst = mst | (win & (u < v))
        parent, _, o4, st = _sharded_contract(
            has, other, n, vps, cap_contract, names, schedule, adaptive,
            st)
    with obs.scope("contract"):
        if relabel_skip:
            lab, settled, o5, st = _relabel_lookup(
                parent, has, lab, settled, vps, cap_label, names, schedule,
                st)
        else:
            lab, _, o5, st = _sharded_lookup(
                parent, lab, compat.vary(jnp.ones((vps,), bool), names),
                vps, cap_label, names, schedule, stats=st, site="relabel")
    o6 = jnp.int32(0)
    if ghost:
        gstate, o6, st = _ghost_push(gstate, parent, vps, cap_push,
                                     cap_push_col, names, schedule, st,
                                     grid_push)
    with obs.scope("contract"):
        go = lax.psum(jnp.sum(has.astype(jnp.int32)), names) > 0
    return (lab, mst, dead, gstate, settled, go,
            o1 + o2 + o3 + o4 + o5 + o6, st)


def _sharded_rounds(u, v, w, eid, valid, lab, mst, dead, gstate, vidx,
                    runs_u, runs_v, n: int, vps: int,
                    axes: Tuple[str, ...], active: Optional[jax.Array],
                    max_rounds: int, cap_edge: int, cap_label: int,
                    cap_lookup: int, cap_push: int, cap_push_col: int,
                    overflow, stats: ExchangeStats, rounds,
                    schedule: str, coalesce: bool, src_only: bool,
                    adaptive: bool, ghost: bool, relabel_skip: bool,
                    pallas_minedges: bool, grid_push: bool):
    """Borůvka rounds with 1D-sharded labels (fused while_loop, flat caps).

    ``active`` optionally restricts the edge set (the filter levels);
    ``dead`` persists across rounds AND levels (once ``ru == rv`` a slot
    is dead forever — labels only coarsen), and so does the ghost state
    — the tables track the *total* label vector, so filter levels reuse
    them.  ``settled`` is per-level: a new weight window revives edges,
    so a component that chose nothing last level may choose again.  The
    loop carry is (lab [vps], mst [cap], dead [cap], gu, gv, rs_row,
    rs_col, settled [vps], go, round, overflow, stats).
    """
    names = tuple(axes)
    live0 = valid if active is None else (valid & active)
    settled0 = compat.vary(jnp.zeros((vps,), bool), names)
    if ghost:
        gu0, gv0, rs0, rsc0 = gstate
    else:
        # 1-element placeholders keep one carry structure for both modes
        gu0 = gv0 = rs0 = rsc0 = compat.vary(
            jnp.zeros((1,), jnp.int32), names)

    def round_(state):
        (lab, mst, dead, gu, gv, rsubs, rsubc, settled, _, r, ovf,
         st) = state
        gs = (gu, gv, rsubs, rsubc) if ghost else None
        lab, mst, dead, gs, settled, go, o, st = _round_body(
            u, v, w, eid, live0, lab, mst, dead, runs_u, runs_v, vidx,
            gs, settled, n, vps, names, cap_edge, cap_label, cap_lookup,
            cap_label, cap_push, cap_push_col, schedule, coalesce,
            src_only, adaptive, ghost, relabel_skip, pallas_minedges,
            grid_push, st)
        if ghost:
            gu, gv, rsubs, rsubc = gs
        return (lab, mst, dead, gu, gv, rsubs, rsubc, settled, go,
                r + 1, ovf + o, st)

    def cond(state):
        return state[8] & (state[9] < max_rounds)

    (lab, mst, dead, gu, gv, rsubs, rsubc, _, _, r, overflow,
     stats) = lax.while_loop(
        cond, round_,
        (lab, mst, dead, gu0, gv0, rs0, rsc0, settled0, jnp.array(True),
         jnp.int32(0), overflow, stats))
    if ghost:
        gstate = (gu, gv, rsubs, rsubc)
    return lab, mst, dead, gstate, overflow, stats, rounds + r


# --------------------------------------------------------------------------
# the full per-shard program + host wrapper
# --------------------------------------------------------------------------

def _sharded_shard_fn(u, v, w, eid, n: int, vps: int,
                      axes: Tuple[str, ...], algorithm: str,
                      num_levels: int, max_rounds: Optional[int],
                      cap_edge: int, cap_label: int, cap_lookup: int,
                      cap_push: int, cap_push_col: int, schedule: str,
                      local_preprocessing: bool, coalesce: bool,
                      src_only: bool, adaptive: bool, ghost: bool,
                      relabel_skip: bool, vsorted: bool,
                      pallas_minedges: bool, grid_push: bool):
    names = tuple(axes)
    valid = jnp.isfinite(w)
    base = lax.axis_index(names) * vps
    lab = base + jnp.arange(vps, dtype=jnp.int32)
    mst = compat.vary(jnp.zeros(u.shape, bool), names)
    # psum outputs are axis-invariant, so the overflow accumulator, the
    # comm counters and the loop's ``go`` flag stay unvarying on both
    # JAX generations
    overflow = jnp.int32(0)
    stats = ExchangeStats.zeros()
    rounds = jnp.int32(0)
    mr = (math.ceil(math.log2(max(n, 2))) + 1) if max_rounds is None \
        else max_rounds

    if local_preprocessing:
        lab, pre_mst, dead, ovf, stats, _ = _sharded_preprocess(
            u, v, w, eid, valid, n, vps, cap_label, names, schedule, stats)
        overflow += ovf
    else:
        pre_mst = compat.vary(jnp.zeros(u.shape, bool), names)
        dead = u == v  # self-loops can never be MSF candidates

    cap = u.shape[0]
    runs_v = None
    if ghost:
        # fused path: ghost tables sized at the safe static bound (one
        # entry per slot); the shrinking driver sizes them host-exactly
        gstate, vidx, runs_u, ovf, stats = _ghost_setup(
            u, v, valid, valid & ~dead, lab, None, n, vps, cap, cap,
            cap_lookup, cap_lookup, cap_label, names, schedule, stats,
            grid_push)
        overflow += ovf
    else:
        gstate = None
        runs_u = run_metadata(u) if (coalesce or src_only) else None
        vidx = _build_v_index(v, valid, n, names) \
            if (coalesce and vsorted) else None
        runs_v = run_metadata(v) if (coalesce and not vsorted) else None

    common = dict(n=n, vps=vps, axes=names, max_rounds=mr,
                  cap_edge=cap_edge, cap_label=cap_label,
                  cap_lookup=cap_lookup, cap_push=cap_push,
                  cap_push_col=cap_push_col,
                  schedule=schedule, coalesce=coalesce, src_only=src_only,
                  adaptive=adaptive, ghost=ghost,
                  relabel_skip=relabel_skip,
                  pallas_minedges=pallas_minedges, grid_push=grid_push)
    if algorithm == "boruvka":
        lab, mst, dead, gstate, overflow, stats, rounds = _sharded_rounds(
            u, v, w, eid, valid, lab, mst, dead, gstate, vidx, runs_u,
            runs_v, active=None, overflow=overflow, stats=stats,
            rounds=rounds, **common)
    elif algorithm == "filter_boruvka":
        pivots = _weight_pivots(w, valid, num_levels, names)
        lo = jnp.float32(-jnp.inf)
        for lvl in range(num_levels):
            hi = pivots[lvl] if lvl < num_levels - 1 else jnp.float32(jnp.inf)
            active = (w > lo) & (w <= hi)
            lab, mst, dead, gstate, overflow, stats, rounds = \
                _sharded_rounds(
                    u, v, w, eid, valid, lab, mst, dead, gstate, vidx,
                    runs_u, runs_v, active=active, overflow=overflow,
                    stats=stats, rounds=rounds, **common)
            lo = hi
    else:
        raise ValueError(algorithm)

    full_mask = mst | pre_mst
    weight = lax.psum(jnp.sum(jnp.where(full_mask, w, 0.0)), names)
    count = lax.psum(jnp.sum(full_mask.astype(jnp.int32)), names)
    comm = CommStats(stats.calls, stats.items, stats.bytes, rounds,
                     stats.hits, stats.misses, stats.pushed,
                     stats.injected)
    return full_mask, weight, count, lab, overflow, comm


@functools.lru_cache(maxsize=64)
def _build_sharded_fn(n: int, vps: int, mesh: jax.sharding.Mesh,
                      axes: Tuple[str, ...], algorithm: str,
                      num_levels: int, max_rounds: Optional[int],
                      cap_edge: int, cap_label: int, cap_lookup: int,
                      cap_push: int, cap_push_col: int, schedule: str,
                      local_preprocessing: bool, coalesce: bool,
                      src_only: bool, adaptive: bool, ghost: bool,
                      relabel_skip: bool, vsorted: bool,
                      pallas_minedges: bool, grid_push: bool):
    fn = partial(_sharded_shard_fn, n=n, vps=vps, axes=axes,
                 algorithm=algorithm, num_levels=num_levels,
                 max_rounds=max_rounds, cap_edge=cap_edge,
                 cap_label=cap_label, cap_lookup=cap_lookup,
                 cap_push=cap_push, cap_push_col=cap_push_col,
                 schedule=schedule,
                 local_preprocessing=local_preprocessing,
                 coalesce=coalesce, src_only=src_only, adaptive=adaptive,
                 ghost=ghost, relabel_skip=relabel_skip, vsorted=vsorted,
                 pallas_minedges=pallas_minedges, grid_push=grid_push)
    spec = P(axes)
    return jax.jit(compat.shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, P(), P(), spec, P(), P())))


# --------------------------------------------------------------------------
# shrinking-capacity driver: one jitted step per round, host-bounded caps
# --------------------------------------------------------------------------

_STAT_FIELDS = 8  # calls/items/bytes/slots, hits/misses/pushed, injected


def _stat_leaves(st: ExchangeStats):
    return (st.calls, st.items, st.bytes, st.slots, st.hits, st.misses,
            st.pushed, st.injected)


def _sharded_prep_shard_fn(u, v, w, eid, n: int, vps: int,
                           axes: Tuple[str, ...], cap_label: int,
                           schedule: str):
    """The preprocessing program.  Returns (lab, pre_mst, dead0, overflow,
    counters, *stat leaves): ``counters`` are ``obs.record``'s, summed
    over the shards (rounds and doubling passes: the most any shard
    ran)."""
    names = tuple(axes)
    valid = jnp.isfinite(w)
    lab, pre_mst, dead0, ovf, st, (rounds, live, steps) = \
        _sharded_preprocess(u, v, w, eid, valid, n, vps, cap_label, names,
                            schedule, ExchangeStats.zeros())
    counters = {"rounds": lax.pmax(rounds, names),
                "live_slots": lax.psum(live, names),
                "slot_rounds": lax.psum(rounds * u.shape[0], names),
                "doubling_steps": lax.pmax(steps, names)}
    return (lab, pre_mst, dead0, ovf, counters) + _stat_leaves(st)


@functools.lru_cache(maxsize=64)
def _build_sharded_prep_fn(n: int, vps: int, mesh: jax.sharding.Mesh,
                           axes: Tuple[str, ...], cap_label: int,
                           schedule: str):
    fn = partial(_sharded_prep_shard_fn, n=n, vps=vps, axes=axes,
                 cap_label=cap_label, schedule=schedule)
    spec = P(axes)
    return jax.jit(compat.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec) + (P(),) * (2 + _STAT_FIELDS)))


def _ghost_setup_shard_fn(u, v, w, dead, vperm, lab, n: int, vps: int,
                          Gu: int, Gv: int, cap_fill_u: int,
                          cap_fill_v: int, cap_sub: int,
                          axes: Tuple[str, ...], schedule: str,
                          grid_push: bool):
    valid = jnp.isfinite(w)
    gstate, _, _, ovf, st = _ghost_setup(
        u, v, valid, valid & ~dead, lab, vperm, n, vps, Gu, Gv,
        cap_fill_u, cap_fill_v, cap_sub, tuple(axes), schedule,
        ExchangeStats.zeros(), grid_push)
    gu, gv, rs_row, rs_col = gstate
    return (gu, gv, rs_row, rs_col, ovf) + _stat_leaves(st)


@functools.lru_cache(maxsize=64)
def _build_ghost_setup_fn(n: int, vps: int, mesh: jax.sharding.Mesh,
                          axes: Tuple[str, ...], Gu: int, Gv: int,
                          cap_fill_u: int, cap_fill_v: int, cap_sub: int,
                          schedule: str, grid_push: bool):
    fn = partial(_ghost_setup_shard_fn, n=n, vps=vps, Gu=Gu, Gv=Gv,
                 cap_fill_u=cap_fill_u, cap_fill_v=cap_fill_v,
                 cap_sub=cap_sub, axes=axes, schedule=schedule,
                 grid_push=grid_push)
    spec = P(axes)
    return jax.jit(compat.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * 6,
        out_specs=(spec, spec, spec, spec) + (P(),) * (1 + _STAT_FIELDS)))


def _sharded_round_shard_fn(u, v, w, eid, vperm, lab, mst, dead, gu, gv,
                            rs_row, rs_col, settled, lo, hi, n: int,
                            vps: int, axes: Tuple[str, ...],
                            cap_edge: int, cap_label: int,
                            cap_lookup: int, cap_contract: int,
                            cap_push: int, cap_push_col: int,
                            schedule: str, coalesce: bool,
                            src_only: bool, adaptive: bool, ghost: bool,
                            relabel_skip: bool, vsorted: bool,
                            pallas_minedges: bool, grid_push: bool):
    names = tuple(axes)
    valid = jnp.isfinite(w)
    live0 = valid & (w > compat.vary(lo, names)) \
        & (w <= compat.vary(hi, names))
    with obs.scope("sort"):
        runs_u = run_metadata(u) if (coalesce or src_only or ghost) \
            else None
        vidx = _build_v_index(v, valid, n, names, perm=vperm) \
            if ((coalesce and vsorted) or ghost) else None
        runs_v = run_metadata(v) if (coalesce and not vsorted) else None
    gstate = (gu, gv, rs_row, rs_col) if ghost else None
    lab, mst, dead, gstate, settled, go, ovf, st = _round_body(
        u, v, w, eid, live0, lab, mst, dead, runs_u, runs_v, vidx,
        gstate, settled, n, vps, names, cap_edge, cap_label, cap_lookup,
        cap_contract, cap_push, cap_push_col, schedule, coalesce,
        src_only, adaptive, ghost, relabel_skip, pallas_minedges,
        grid_push, ExchangeStats.zeros())
    if ghost:
        gu, gv, rs_row, rs_col = gstate
    return (lab, mst, dead, gu, gv, rs_row, rs_col, settled, go,
            ovf) + _stat_leaves(st)


@functools.lru_cache(maxsize=256)
def _build_sharded_round_fn(n: int, vps: int, mesh: jax.sharding.Mesh,
                            axes: Tuple[str, ...], cap_edge: int,
                            cap_label: int, cap_lookup: int,
                            cap_contract: int, cap_push: int,
                            cap_push_col: int, schedule: str,
                            coalesce: bool, src_only: bool,
                            adaptive: bool, ghost: bool,
                            relabel_skip: bool, vsorted: bool,
                            pallas_minedges: bool, grid_push: bool):
    fn = partial(_sharded_round_shard_fn, n=n, vps=vps, axes=axes,
                 cap_edge=cap_edge, cap_label=cap_label,
                 cap_lookup=cap_lookup, cap_contract=cap_contract,
                 cap_push=cap_push, cap_push_col=cap_push_col,
                 schedule=schedule, coalesce=coalesce,
                 src_only=src_only, adaptive=adaptive, ghost=ghost,
                 relabel_skip=relabel_skip, vsorted=vsorted,
                 pallas_minedges=pallas_minedges, grid_push=grid_push)
    spec = P(axes)
    return jax.jit(compat.shard_map(
        fn, mesh=mesh,
        in_specs=(spec,) * 13 + (P(), P()),
        out_specs=(spec,) * 8 + (P(),) * (2 + _STAT_FIELDS)))


def _host_weight_pivots(w_h: np.ndarray, valid_h: np.ndarray,
                        num_levels: int, p: int, cap: int) -> np.ndarray:
    """Host replica of ``_weight_pivots`` (identical sampling discipline:
    same per-shard stride-64 sample, same gather order, same quantile
    positions), so the shrinking driver buckets the filter levels exactly
    like the fused engine and the two paths stay bit-identical."""
    s = min(64, cap)
    idx = (np.arange(s) * cap) // s
    samp = []
    for sh in range(p):
        ws = w_h[sh * cap:(sh + 1) * cap]
        vs = valid_h[sh * cap:(sh + 1) * cap]
        samp.append(np.where(vs[idx], ws[idx], np.inf))
    all_samp = np.sort(np.concatenate(samp).astype(np.float32))
    nfin = max(int(np.isfinite(all_samp).sum()), 1)
    pos = (np.arange(1, num_levels) * nfin) // num_levels
    return all_samp[pos]


def minedges_buffer_bytes(p: int, capacity: int, hops: int,
                          src_only: bool) -> int:
    """Static buffer bytes one MINEDGES phase ships at ``capacity``.

    Mirrors comm/exchange.py's capacity-padded accounting: a candidate
    exchange ships four [p, C] payload buffers (i32/f32/i32/i32) plus
    the 1-byte validity mask, each hop; the confirmation reply ships one
    [p, C] bool buffer.  src-only pays that once, the 2-exchange
    baseline twice.  The shrinking-capacity driver uses this to expose
    the per-round MINEDGES buffer-bytes trajectory in ``round_trace``
    (the dominant term the schedule exists to shrink).
    """
    per_exchange = (4 * 4 + 1) * p * capacity * hops
    per_reply = 1 * p * capacity * hops
    k = 1 if src_only else 2
    return k * (per_exchange + per_reply)


def _per_pair_max(shard: np.ndarray, owner: np.ndarray, p: int) -> int:
    """Max count over (source shard, destination owner) pairs."""
    if owner.size == 0:
        return 0
    return int(np.bincount(shard * p + owner, minlength=p * p).max())


def _host_run_heads(a, num_shards: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host mirror of ``kernels/segmin run_metadata``: per-shard
    contiguous equal-value run structure of a shard-major array.

    Returns (heads [p * cap] bool — first slot of its run, with a head
    forced at every shard start, exactly like the device computes runs
    per shard — and rid [p * cap] int, globally numbered run ids).
    Shared by every host-side capacity bound so the run definition
    cannot diverge between them.
    """
    arr = np.asarray(a)
    cap = arr.shape[0] // num_shards
    a2 = arr.reshape(num_shards, cap)
    head = np.ones((num_shards, cap), bool)
    head[:, 1:] = a2[:, 1:] != a2[:, :-1]
    flat = head.reshape(-1)
    return flat, np.cumsum(flat) - 1


def _minedges_capacity_bound(ru: np.ndarray, rv: np.ndarray,
                             alive: np.ndarray, shard: np.ndarray,
                             heads: np.ndarray, rid: np.ndarray,
                             p: int, vps: int, src_only: bool) -> int:
    """Exact MINEDGES candidate-exchange capacity for the coming round.

    The host holds the full sharded label table between rounds, so the
    candidate set — live slots whose endpoint components differ — and
    its owner-keyed distribution are computable exactly: the capacity is
    the maximum number of candidates any shard sends any owner.  In
    src-only mode candidates are aggregated per source run
    (``_sharded_minedges_src``), so the count is over *alive runs* keyed
    by the run's component owner; the 2-exchange variant counts alive
    slots under both endpoint keys.  Exact means the smaller buffers
    stay overflow-free by construction, and the bound decays with the
    alive-run / cross-component structure instead of staying at
    edges/shard.  Returns 0 when no candidate exists (the round could
    choose nothing).
    """
    if not alive.any():
        return 0
    if src_only:
        run_alive = np.bincount(rid[alive],
                                minlength=int(rid[-1]) + 1) > 0
        cand = heads & run_alive[rid]
        return _per_pair_max(shard[cand], ru[cand] // vps, p)
    sa = shard[alive]
    return max(_per_pair_max(sa, ru[alive] // vps, p),
               _per_pair_max(sa, rv[alive] // vps, p))


def _endpoint_lookup_bound(u_h: np.ndarray, v_h: np.ndarray,
                           live_h: np.ndarray, shard: np.ndarray,
                           p: int, vps: int) -> int:
    """Exact per-(shard, owner) bound for the *uncoalesced* endpoint
    lookups: every live slot requests both its endpoints' owners."""
    sl = shard[live_h]
    if sl.size == 0:
        return 1
    return max(1, _per_pair_max(sl, u_h[live_h] // vps, p),
               _per_pair_max(sl, v_h[live_h] // vps, p))


def _host_v_perm(v_h: np.ndarray, valid_h: np.ndarray, n: int,
                 p: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host mirror of ``_build_v_index``: per-shard stable argsort of the
    big-keyed v column.  Returns (perm [p * cap] int32 — local indices
    per shard, skey [p * cap] — the sorted keys, padding = n at each
    shard's tail).  Any stable sort of the same keys yields the same run
    structure, so host and device indices are interchangeable."""
    cap = v_h.shape[0] // p
    key = np.where(valid_h, v_h, n).astype(np.int64).reshape(p, cap)
    perm = np.argsort(key, axis=1, kind="stable").astype(np.int32)
    skey = np.take_along_axis(key, perm, axis=1)
    return perm.reshape(-1), skey.reshape(-1)


def _host_run_count_max(heads: np.ndarray, p: int) -> int:
    """Max per-shard run count — the host-exact ghost-table size."""
    cap = heads.shape[0] // p
    return max(1, int(heads.reshape(p, cap).sum(axis=1).max()))


def _host_ghost_lists(u_h: np.ndarray, v_h: np.ndarray,
                      live_h: np.ndarray, p: int) -> List[np.ndarray]:
    """Per shard: the distinct endpoint vids of its live slots — the
    host mirror of each shard's filled ghost-entry set (live-gated:
    all-dead runs are never read again, so they are never filled or
    subscribed)."""
    out = []
    cap = u_h.shape[0] // p
    for s in range(p):
        sl = slice(s * cap, (s + 1) * cap)
        out.append(np.unique(np.concatenate([u_h[sl][live_h[sl]],
                                             v_h[sl][live_h[sl]]])))
    return out


def _subscribe_capacity_bound(lab_h: np.ndarray,
                              ghosts: List[np.ndarray], p: int,
                              vps: int) -> int:
    """Exact per-(shard, owner) row count of the setup root-subscribe
    exchange: one row per distinct cached component root per shard."""
    mx = 1
    for gh in ghosts:
        if gh.size:
            roots = np.unique(lab_h[gh])
            mx = max(mx, int(np.bincount(roots // vps,
                                         minlength=p).max()))
    return mx


def _ghost_fill_bounds(u_h: np.ndarray, live_h: np.ndarray,
                       vperm_h: np.ndarray, skey: np.ndarray, n: int,
                       p: int, vps: int) -> Tuple[int, int]:
    """Exact per-(shard, owner) request counts of the two ghost fills:
    one request per distinct endpoint value with >= 1 live slot (u in
    slot order, v through the sorted key column)."""
    cap = u_h.shape[0] // p
    shard = np.repeat(np.arange(p), cap)
    head_u, rid_u = _host_run_heads(u_h, p)
    run_live = np.bincount(rid_u[live_h],
                           minlength=int(rid_u[-1]) + 1) > 0
    send_u = head_u & run_live[rid_u]
    bu = max(1, _per_pair_max(shard[send_u], u_h[send_u] // vps, p))
    head_v, rid_v = _host_run_heads(skey, p)
    live_p = np.take_along_axis(live_h.reshape(p, cap),
                                vperm_h.reshape(p, cap), axis=1
                                ).reshape(-1)
    run_live_v = np.bincount(rid_v[live_p],
                             minlength=int(rid_v[-1]) + 1) > 0
    send_v = head_v & (skey < n) & run_live_v[rid_v]
    bv = max(1, _per_pair_max(shard[send_v],
                              (skey[send_v] // vps).astype(np.int64), p))
    return bu, bv


def _relabel_capacity_bound(lab_h: np.ndarray, settled_h: np.ndarray,
                            p: int, vps: int) -> int:
    """Exact per-(shard, owner) RELABEL request count under the
    settled-vertex skip: vertex x requests from ``owner(lab[x])`` iff it
    has not yet observed its component choose nothing.  ``settled_h`` is
    the host mirror of the device mask (identical update rule, so the
    request sets coincide at overflow 0)."""
    req = ~settled_h
    if not req.any():
        return 1
    x = np.nonzero(req)[0]
    return max(1, _per_pair_max(x // vps, lab_h[x] // vps, p))


def _push_capacity_bound(lab_h: np.ndarray, ghosts: List[np.ndarray],
                         choosing: np.ndarray, p: int, vps: int) -> int:
    """Upper bound on the round's root-delta push and forward rows.

    Only a root that chose an edge this round can merge (dirty roots ⊆
    choosing), and the device's ``root_subs`` at round start is exactly
    "shards whose cached entry set contains the root" — which the host
    reconstructs from the current label table over the static ghost
    lists, so no incremental mirror of the forwarding is needed.  The
    bound covers both leg shapes: push copies per (owner shard,
    subscriber) and forward rows per source shard (a forward's
    destination is the unknown surviving root's owner, so the per-source
    total bounds every (source, dest) pair).  Decays geometrically with
    the alive-component count — the whole point of keying the dirty set
    by root instead of by vertex."""
    per_pair = np.zeros((p, p), np.int64)  # [owner, subscriber]
    subscribed = []
    for s, gh in enumerate(ghosts):
        if gh.size == 0:
            continue
        roots = np.unique(lab_h[gh])
        roots = roots[choosing[roots]]
        if roots.size == 0:
            continue
        per_pair[:, s] = np.bincount(roots // vps, minlength=p)
        subscribed.append(roots)
    if not subscribed:
        return 1
    all_roots = np.unique(np.concatenate(subscribed))
    fw = int(np.bincount(all_roots // vps, minlength=p).max())
    return max(1, int(per_pair.max()), fw)


def _push_capacity_bound_grid(lab_h: np.ndarray, ghosts: List[np.ndarray],
                              choosing: np.ndarray, p: int, R: int,
                              C: int, vps: int) -> Tuple[int, int]:
    """Host-exact bounds for the two-level grid push (ISSUE 10).

    Same reconstruction discipline as ``_push_capacity_bound``, but the
    device state is now a (row mask, col mask) *pair* per owned root, so
    the two hops have distinct shapes to bound:

      * hop 1 (owner → deputy): copies per (owner shard, destination
        column) — one per dirty root whose col mask has that column's
        bit.  The forward leg (merged masks to the surviving root's
        owner) shares ``cap_row``, so its per-source row count folds in.
      * hop 2 (deputy → subscriber): copies per (deputy device,
        destination row) — a deputy at (ri, cc) relays exactly the dirty
        roots whose owner sits in row ri, whose col mask contains cc,
        and whose row mask contains the destination row.

    Over-delivery is part of the contract: the bounds count the *cross
    product* of the per-axis masks, exactly what the device ships.
    Returns ``(bound_row, bound_col)``, each >= 1.
    """
    nv = p * vps
    row_mask = np.zeros(nv, np.int64)
    col_mask = np.zeros(nv, np.int64)
    for s, gh in enumerate(ghosts):
        if gh.size == 0:
            continue
        roots = np.unique(lab_h[gh])
        roots = roots[choosing[roots]]
        if roots.size == 0:
            continue
        row_mask[roots] |= np.int64(1) << (s // C)
        col_mask[roots] |= np.int64(1) << (s % C)
    dirty = np.nonzero(row_mask)[0]
    if dirty.size == 0:
        return 1, 1
    owner = dirty // vps
    # hop 1: [owner shard, dest col] copy counts
    b_row = 1
    for cc in range(C):
        has = ((col_mask[dirty] >> cc) & 1) > 0
        if has.any():
            b_row = max(b_row, int(np.bincount(owner[has],
                                               minlength=p).max()))
    # forward leg shares cap_row: rows per source shard
    b_row = max(b_row, int(np.bincount(owner, minlength=p).max()))
    # hop 2: [deputy device, dest row] copy counts; deputy (ri, cc)
    # relays roots owned in row ri with col bit cc, per dest-row bit
    b_col = 1
    orow = owner // C
    for rr in range(R):
        to_rr = ((row_mask[dirty] >> rr) & 1) > 0
        if not to_rr.any():
            continue
        for cc in range(C):
            sel = to_rr & (((col_mask[dirty] >> cc) & 1) > 0)
            if sel.any():
                b_col = max(b_col, int(np.bincount(orow[sel],
                                                   minlength=R).max()))
    return b_row, b_col


def _contract_capacity_bound(ru: np.ndarray, rv: np.ndarray,
                             alive: np.ndarray, vps: int) -> int:
    """Max per-owner count of distinct components incident to candidate
    edges.

    Bounds the contract-phase exchange rows exactly: only a component
    with a chosen edge has a non-self parent (so only those slots
    request, see ``_sharded_contract``), a choosing component received
    at least one candidate, and the requesting set only shrinks as
    doubling converges.  ``ru``/``rv`` are the host-resolved endpoint
    components — the same values the device lookups will produce.
    """
    if not alive.any():
        return 1
    comp = np.unique(np.concatenate([ru[alive], rv[alive]]))
    return max(1, int(np.bincount(comp // vps).max()))


def _certified_checkpoint(graph, n, mesh, axes, p, cap, algorithm,
                          windows, rounds, lvl_next, r_next, plan_pos,
                          lab, mask_h, dead_h, settled_h, ghost_on, acc):
    """Invariant barrier + snapshot (ISSUE 9): run the on-device
    ``core/verify.py`` structural checks against the partial forest and
    only construct the ``MSFCheckpoint`` on a pass — labels are
    fixpoints at every round boundary and each chosen edge merges
    exactly two components, so the mid-run forest satisfies the same
    invariants as the final one.  A failing barrier returns ``None``
    (no checkpoint beats an uncertified one)."""
    from repro.core.verify import verify_forest
    rep = verify_forest(graph, n, mesh, jnp.asarray(mask_h), lab,
                        axis_names=axes, raise_on_fail=False)
    if not rep.ok:
        return None
    return MSFCheckpoint.create(
        n=n, num_shards=p, cap_per_shard=cap, algorithm=algorithm,
        round_index=rounds, level=lvl_next, round_in_level=r_next,
        plan_pos=plan_pos, level_bounds=windows,
        lab=np.asarray(lab), settled=settled_h, mask=mask_h,
        dead=dead_h, eid=np.asarray(graph.eid), ghost_on=ghost_on,
        stats_acc=acc)


def _shrinking_capacity_msf(graph: DistGraph, n: int,
                            mesh: jax.sharding.Mesh, axes: Tuple[str, ...],
                            algorithm: str, num_levels: int,
                            max_rounds: Optional[int], ce_full: int,
                            cl: int, lk_full: int, schedule: str,
                            local_preprocessing: bool, coalesce: bool,
                            src_only: bool, adaptive: bool, ghost: bool,
                            relabel_skip: bool, vsorted: bool,
                            push_capacity: Optional[int],
                            round_trace: Optional[List[dict]],
                            plan_out: Optional[dict] = None,
                            pallas_minedges: bool = False,
                            grid_push: bool = False,
                            ckpt_every: Optional[int] = None,
                            ckpt_out: Optional[List] = None,
                            resume_from: Optional[MSFCheckpoint] = None):
    """Host-orchestrated rounds with per-round shrinking capacities.

    Runs the same ``_round_body`` as the fused engine, one jitted step
    per round, sizing each round's exchanges from host-side bounds on
    the measured dead-edge mask (see module docstring).  Bounds are
    snapped up to the ``shrink_schedule`` ladder so the set of compiled
    step programs stays logarithmic and strictly reusable across rounds
    and solves.  At overflow 0 (guaranteed for default capacities — the
    bounds are exact by construction) the result is bit-identical to the
    flat-capacity engine; the only observable difference is that a level
    whose host bound hits zero skips its trailing empty round, which can
    only *reduce* the round count.

    Ghost additions (ISSUE 4): the ghost tables are sized host-exactly
    (max per-shard distinct-endpoint run count), the fills at the exact
    distinct-value bounds, the per-round root-delta push at the
    subscribed-choosing-root bound (reconstructed from the label table
    over the static ghost lists each round), and the RELABEL capacity at
    the unsettled-request bound (the host mirrors the device's monotone
    ``settled`` mask with the identical update rule).  A user-pinned
    ``push_capacity`` below the round's push bound triggers the
    **graceful exact fallback**: the driver abandons the cache and
    finishes with exact coalesced lookups — results stay exact at
    overflow 0, never silently wrong (the fused engine instead reports
    push overflow, same contract as every exchange).

    Planner backend (ISSUE 5): with ``plan_out`` (a dict) the driver
    doubles as the measurement pass of ``plan_sharded_msf`` — it
    records the one-off setup capacities, the level weight windows and
    one ``RoundSpec`` per round with exactly the ladder-snapped
    capacities it executed.  When a level ends because the host bound
    hit zero candidates, the driver skips that trailing empty round but
    records it as a **sentinel** spec at floor capacities: the unrolled
    executor runs it, and its ``go`` flag re-proves in-program — on
    every replay graph — what the zero bound proved on the host here.
    """
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    if grid_push and len(axes) != 2:
        raise ValueError(
            f"grid_push needs a 2-axis (row, col) mesh, got axes={axes}")
    R = mesh.shape[axes[0]] if len(axes) == 2 else p
    C = mesh.shape[axes[1]] if len(axes) == 2 else 1
    vps = vertices_per_shard(n, p)
    cap = graph.cap_total // p
    mr = (math.ceil(math.log2(max(n, 2))) + 1) if max_rounds is None \
        else max_rounds
    with obs.span("driver.readback"):
        u_h = np.asarray(graph.u)
        v_h = np.asarray(graph.v)
        w_h = np.asarray(graph.w)
        valid_h = np.isfinite(w_h)
    hops = _hops(axes, schedule)

    if plan_out is not None and (resume_from is not None or ckpt_every):
        raise ValueError(
            "checkpointing is not supported during plan measurement; "
            "checkpoint the planned execution via execute_plan instead")

    overflow = 0
    acc = np.zeros(_STAT_FIELDS, np.float64)
    prep_counters = {}
    if resume_from is not None:
        # re-entry (ISSUE 9): the certified snapshot replaces the
        # preprocessing product wholesale — labels, masks and position
        # restore bit-exactly, and the ghost tables are rebuilt below
        # through the existing setup path from the restored (lab, dead)
        ck = resume_from.validate_for(n, p, cap)
        if ck.algorithm != algorithm:
            raise CheckpointError(
                f"checkpoint algorithm {ck.algorithm!r} does not match "
                f"this solve's {algorithm!r}")
        lab = jnp.asarray(ck.lab)
        pre_mst = jnp.zeros((p * cap,), bool)
        mst = jnp.asarray(ck.mask)
        dead = jnp.asarray(ck.dead)
        acc += ck.stats_acc
        ghost = ghost and ck.ghost_on
    elif local_preprocessing:
        with obs.span("driver.prep"):
            prep = _build_sharded_prep_fn(n, vps, mesh, tuple(axes), cl,
                                          schedule)
            lab, pre_mst, dead, ovf, prep_counters, *st = prep(
                graph.u, graph.v, graph.w, graph.eid)
            overflow += int(ovf)
            acc += [float(x) for x in st]
        mst = jnp.zeros((p * cap,), bool)
    else:
        lab = jnp.arange(p * vps, dtype=jnp.int32)
        pre_mst = jnp.zeros((p * cap,), bool)
        dead = jnp.asarray(u_h == v_h)
        mst = jnp.zeros((p * cap,), bool)

    # static host structures: source-run heads (src-only aggregation +
    # u-side fill bound) and the v-sorted secondary index
    with obs.span("driver.index"):
        dead_h = np.asarray(dead)
        shard_of = np.repeat(np.arange(p), cap)
        heads, rid = _host_run_heads(u_h, p)
        vperm_h, skey = _host_v_perm(v_h, valid_h, n, p)
        vperm = jnp.asarray(vperm_h.astype(np.int32))

    ghost_on = ghost
    ghosts = None
    if ghost_on:
        with obs.span("driver.ghost_bounds"):
            live_setup = valid_h & ~dead_h
            Gu = _host_run_count_max(heads, p)
            Gv = _host_run_count_max(_host_run_heads(skey, p)[0], p)
            ghosts = _host_ghost_lists(u_h, v_h, live_setup, p)
            bu, bv = _ghost_fill_bounds(u_h, live_setup, vperm_h, skey, n,
                                        p, vps)
            bs = _subscribe_capacity_bound(np.asarray(lab), ghosts, p, vps)
            qfu = quantize_capacity(bu, lk_full)
            qfv = quantize_capacity(bv, lk_full)
            qsub = quantize_capacity(bs, vps)
            if plan_out is not None:
                plan_out["ghost"] = GhostPlan(Gu, Gv, qfu, qfv, qsub)
        with obs.span("driver.ghost_setup"):
            setup = _build_ghost_setup_fn(
                n, vps, mesh, tuple(axes), Gu, Gv, qfu, qfv, qsub, schedule,
                grid_push)
            gu, gv, rsubs_dev, rsubc_dev, ovf, *st = setup(
                graph.u, graph.v, graph.w, dead, vperm, lab)
            overflow += int(ovf)
            acc += [float(x) for x in st]
    else:
        gu = gv = jnp.zeros((p,), jnp.int32)  # [1] per shard placeholder
        rsubs_dev = jnp.zeros((p,), jnp.int32)
        rsubc_dev = jnp.zeros((p,), jnp.int32)

    if algorithm == "boruvka":
        windows = [(-np.inf, np.inf)]
    elif algorithm == "filter_boruvka":
        piv = _host_weight_pivots(w_h, valid_h, num_levels, p, cap)
        edges_hi = [float(x) for x in piv]
        los = [-np.inf] + edges_hi
        his = edges_hi + [np.inf]
        windows = list(zip(los, his))
    else:
        raise ValueError(algorithm)
    if resume_from is not None:
        # the snapshot freezes the level windows: recomputing pivots on
        # a different mesh (elastic restore) could move them, and the
        # bit-identity contract needs the original partition of work
        windows = [(float(lo), float(hi))
                   for lo, hi in resume_from.level_bounds]
    if plan_out is not None:
        plan_out["level_bounds"] = [(float(lo), float(hi))
                                    for lo, hi in windows]
        plan_out["rounds"] = []

    rounds = 0
    step_live = []  # alive slots of each dispatched round step
    start_lvl = start_r = 0
    settled_resume = None
    if resume_from is not None:
        rounds = resume_from.round_index
        start_lvl = resume_from.level
        start_r = resume_from.round_in_level
        settled_resume = resume_from.settled
    for lvl, (lo, hi) in enumerate(windows):
        if lvl < start_lvl:
            continue
        with obs.span("driver.bounds"):
            active_h = valid_h & (w_h > lo) & (w_h <= hi)
            # settled is per level: a new weight window revives edges
            if lvl == start_lvl and settled_resume is not None:
                settled_dev = jnp.asarray(settled_resume)
                settled_h = settled_resume.copy()
                r = start_r
            else:
                settled_dev = jnp.zeros((p * vps,), bool)
                settled_h = np.zeros(p * vps, bool)
                r = 0
        while r < mr:
            if overflow:
                # a user-undersized capacity already dropped items: the
                # result is unreliable by contract (caller must retry
                # larger), and garbage labels would poison the host
                # bounds — stop burning rounds and report
                break
            with obs.span("driver.bounds"):
                live_h = active_h & ~dead_h
                lab_h = np.asarray(lab)
                ru_h = lab_h[u_h]
                rv_h = lab_h[v_h]
                alive_h = live_h & (ru_h != rv_h)
                n_alive = int(np.count_nonzero(alive_h))
                bound_e = _minedges_capacity_bound(ru_h, rv_h, alive_h,
                                                   shard_of, heads, rid, p,
                                                   vps, src_only)
                ce_r = quantize_capacity(bound_e, ce_full)
                choosing = np.zeros(p * vps, bool)
                choosing[np.unique(ru_h[alive_h])] = True
                ghost_round = ghost_on
                cp_r = 1
                cpc_r = 0
                pb_flat = 0
                if ghost_round:
                    pb_flat = _push_capacity_bound(lab_h, ghosts, choosing,
                                                   p, vps)
                    if grid_push:
                        pb, pbc = _push_capacity_bound_grid(
                            lab_h, ghosts, choosing, p, R, C, vps)
                        # the deputy hop's ceiling is every owned root once
                        # per source column; C*vps always holds a rung >= pbc
                        cpc_r = quantize_capacity(pbc, C * vps)
                    else:
                        pb = pb_flat
                    cp_r = quantize_capacity(pb, vps) \
                        if push_capacity is None else int(push_capacity)
                    if cp_r < pb:
                        # graceful exact fallback: a user-pinned push
                        # capacity that cannot hold the worst-case dirty set
                        # would leave stale ghost entries; abandon the cache
                        # and finish with exact coalesced lookups instead of
                        # risking a wrong (if reported) answer
                        ghost_on = ghost_round = False
                        cp_r = 1
                        cpc_r = 0
                coalesce_eff = coalesce or (ghost and not ghost_round)
                # after a ghost fallback the v-sorted machinery is already
                # built, so the fallback lookups always use it
                vsorted_eff = vsorted or (ghost and not ghost_round)
                if ghost_round:
                    lk_r = 1  # no endpoint lookups are traced
                elif coalesce_eff:
                    lk_r = quantize_capacity(
                        default_lookup_capacity(graph, p, n, alive=live_h,
                                                vsorted=vsorted_eff,
                                                vindex=(vperm_h, skey)),
                        lk_full)
                else:
                    lk_r = quantize_capacity(
                        _endpoint_lookup_bound(u_h, v_h, live_h, shard_of,
                                               p, vps), lk_full)
                con_r = quantize_capacity(
                    _contract_capacity_bound(ru_h, rv_h, alive_h, vps), cl)
                if relabel_skip:
                    rl_r = quantize_capacity(
                        _relabel_capacity_bound(lab_h, settled_h, p, vps), cl)
                else:
                    rl_r = cl
                if plan_out is not None:
                    plan_out["rounds"].append(RoundSpec(
                        level=lvl, cap_edge=ce_r, cap_lookup=lk_r,
                        cap_contract=con_r, cap_relabel=rl_r, cap_push=cp_r,
                        ghost=bool(ghost_round), sentinel=(bound_e == 0),
                        cap_push_col=cpc_r))
            if bound_e == 0:
                break  # no candidate exists: go would come back False
            # publish the 1-based round for abort-kind fault specs
            # (no-op unless an abort spec is active)
            faults.set_round(rounds + 1)
            with obs.span("driver.step") as span:
                step = _build_sharded_round_fn(
                    n, vps, mesh, tuple(axes), ce_r, rl_r, lk_r, con_r,
                    cp_r, cpc_r, schedule, coalesce_eff, src_only, adaptive,
                    ghost_round, relabel_skip, vsorted_eff, pallas_minedges,
                    grid_push and ghost_round)
                (lab, mst, dead, gu, gv, rsubs_dev, rsubc_dev, settled_dev,
                 go, ovf, *st) = step(
                    graph.u, graph.v, graph.w, graph.eid, vperm, lab, mst,
                    dead, gu, gv, rsubs_dev, rsubc_dev, settled_dev,
                    jnp.float32(lo), jnp.float32(hi))
                overflow += int(ovf)
                acc += [float(x) for x in st]
                dead_h = np.asarray(dead)
                if relabel_skip:
                    # mirror of the device's monotone settled update: a
                    # requesting vertex settles iff its (pre-contraction)
                    # component chose nothing this round
                    settled_h = settled_h | ~choosing[lab_h]
                rounds += 1
                r += 1
                rec = {
                    "round": rounds, "level": lvl,
                    "cap_edge": ce_r, "cap_lookup": lk_r,
                    "cap_contract": con_r, "cap_relabel": rl_r,
                    "cap_push": cp_r, "cap_push_col": cpc_r,
                    "cap_push_flat": pb_flat,
                    "grid_push": bool(grid_push and ghost_round),
                    "ghost": bool(ghost_round),
                    "alive_bound": bound_e,
                    "minedges_buffer_bytes": minedges_buffer_bytes(
                        p, ce_r, hops, src_only),
                    "a2a_calls": int(st[0]),
                    "routed_items": float(st[1]),
                    "buffer_bytes": float(st[2]),
                    "buffer_slots": float(st[3]),
                    "cache_hits": float(st[4]),
                    "lookup_items": float(st[5]),
                    "pushed_items": float(st[6]),
                    "injected_items": float(st[7]),
                }
                # one record per round: the step span's args and the
                # caller's round_trace entry
                span.set_metadata(**rec)
            step_live.append(n_alive)
            if round_trace is not None:
                round_trace.append(rec)
            if (ckpt_out is not None and ckpt_every
                    and rounds % ckpt_every == 0 and not overflow):
                # cadence boundary: certify, then snapshot the re-entry
                # position — mid-level if the level continues, else the
                # head of the next level with a fresh settled mask
                nxt_lvl, nxt_r = (lvl, r) if bool(go) else (lvl + 1, 0)
                sh = settled_h if bool(go) else np.zeros(p * vps, bool)
                mask_now = np.asarray(mst) | np.asarray(pre_mst)
                ck = _certified_checkpoint(
                    graph, n, mesh, axes, p, cap, algorithm, windows,
                    rounds, nxt_lvl, nxt_r, None, lab, mask_now,
                    dead_h, sh, ghost_on, acc)
                if ck is not None:
                    ckpt_out.append(ck)
            if not bool(go):
                break

    with obs.span("driver.finish"):
        mask = np.asarray(mst) | np.asarray(pre_mst)
        weight = np.float32(np.sum(w_h[mask], dtype=np.float64))
        count = np.int32(int(mask.sum()))
        comm = CommStats(np.int32(acc[0]), np.float32(acc[1]),
                         np.float32(acc[2]), np.int32(rounds),
                         np.float32(acc[4]), np.float32(acc[5]),
                         np.float32(acc[6]), np.float32(acc[7]))
        out = (jnp.asarray(mask), weight, count, lab, np.int32(overflow),
               comm)
    obs.record(rounds=[prep_counters.get("rounds", 0), len(step_live)],
               live_slots=[prep_counters.get("live_slots", 0), step_live],
               slot_rounds=[prep_counters.get("slot_rounds", 0),
                            len(step_live) * p * cap],
               doubling_steps=prep_counters.get("doubling_steps", 0))
    return out


# --------------------------------------------------------------------------
# plan / execute split (ISSUE 5): the shrinking schedule as a value
# --------------------------------------------------------------------------

def _planned_shard_fn(u, v, w, eid, n: int, vps: int,
                      axes: Tuple[str, ...], plan: RoundPlan):
    """The plan executor: a Python-unrolled multi-round program.

    One straight-line per-shard program for the whole solve — the same
    setup phases and the same ``_round_body`` as the fused engine, but
    with *per-round* static capacities read off the ``RoundPlan``
    instead of one flat worst case, so the program jits and AOT-lowers
    whole while its buffers follow the measured shrinking schedule.

    Replay safety (never silent): besides the usual per-exchange
    overflow accounting, two plan-specific hazards are surfaced —

      * **ghost table capacity**: a replay graph with more distinct
        endpoint runs than the planned tables would have fills
        silently dropped (``mode="drop"``) and later read a *clipped*
        table entry; the per-shard run counts are therefore compared
        against the planned sizes and any excess is charged to
        ``overflow``;
      * **residual rounds**: each level's final planned round (a
        sentinel at floor capacities when the measurement pass bounded
        the level to zero remaining candidates) re-computes ``go``; a
        level still choosing edges after its last planned round sets
        the ``residual`` output, which the host wrapper turns into a
        replan and the AOT path folds into ``overflow``.

    Returns (mask, weight, count, lab, overflow, residual, comm) —
    the fused engine's tuple plus the residual-level count.
    """
    names = tuple(axes)
    valid = jnp.isfinite(w)
    base = lax.axis_index(names) * vps
    lab = base + jnp.arange(vps, dtype=jnp.int32)
    mst = compat.vary(jnp.zeros(u.shape, bool), names)
    overflow = jnp.int32(0)
    stats = ExchangeStats.zeros()

    if plan.local_preprocessing:
        lab, pre_mst, dead, ovf, stats, _ = _sharded_preprocess(
            u, v, w, eid, valid, n, vps, plan.cap_prep, names,
            plan.schedule, stats)
        overflow += ovf
    else:
        pre_mst = compat.vary(jnp.zeros(u.shape, bool), names)
        dead = u == v

    runs_v = None
    if plan.ghost is not None:
        gp = plan.ghost
        gstate, vidx, runs_u, ovf, stats = _ghost_setup(
            u, v, valid, valid & ~dead, lab, None, n, vps, gp.table_u,
            gp.table_v, gp.cap_fill_u, gp.cap_fill_v, gp.cap_subscribe,
            names, plan.schedule, stats, plan.grid_push)
        overflow += ovf
        # ghost-table structural guard (see docstring): excess distinct
        # runs over the planned table sizes are dropped fills — report
        nu = lax.pmax(jnp.sum(runs_u[0].astype(jnp.int32)), names)
        nv = lax.pmax(jnp.sum(vidx.runs[0].astype(jnp.int32)), names)
        overflow += jnp.maximum(nu - gp.table_u, 0) \
            + jnp.maximum(nv - gp.table_v, 0)
    else:
        gstate = None
        runs_u = run_metadata(u) if (plan.coalesce or plan.src_only) \
            else None
        vidx = _build_v_index(v, valid, n, names) \
            if (plan.coalesce and plan.vsorted_index) else None
        runs_v = run_metadata(v) \
            if (plan.coalesce and not plan.vsorted_index) else None

    residual = jnp.int32(0)
    for lvl, (lo, hi) in enumerate(plan.level_bounds):
        live0 = valid
        if len(plan.level_bounds) > 1:
            live0 = valid & (w > jnp.float32(lo)) & (w <= jnp.float32(hi))
        settled = compat.vary(jnp.zeros((vps,), bool), names)
        go = None
        for spec in plan.rounds:
            if spec.level != lvl:
                continue
            # the driver's effective-lever rules, frozen per round: a
            # non-ghost round of a ghost plan is the graceful fallback,
            # which always runs coalesced through the v-sorted index
            fallback = plan.ghost is not None and not spec.ghost
            coalesce_eff = plan.coalesce or fallback
            vidx_r = vidx if (spec.ghost
                              or (coalesce_eff and vidx is not None)) \
                else None
            lab, mst, dead, gstate, settled, go, o, stats = _round_body(
                u, v, w, eid, live0, lab, mst, dead, runs_u, runs_v,
                vidx_r, gstate, settled, n, vps, names, spec.cap_edge,
                spec.cap_relabel, spec.cap_lookup, spec.cap_contract,
                spec.cap_push, spec.cap_push_col, plan.schedule,
                coalesce_eff, plan.src_only, plan.adaptive_doubling,
                spec.ghost, plan.relabel_skip, plan.pallas_minedges,
                plan.grid_push and spec.ghost, stats)
            overflow += o
        if go is not None:
            # a level still choosing edges after its planned rounds has
            # residual work the plan did not provision
            residual += go.astype(jnp.int32)

    full_mask = mst | pre_mst
    weight = lax.psum(jnp.sum(jnp.where(full_mask, w, 0.0)), names)
    count = lax.psum(jnp.sum(full_mask.astype(jnp.int32)), names)
    comm = CommStats(stats.calls, stats.items, stats.bytes,
                     jnp.int32(plan.num_rounds), stats.hits,
                     stats.misses, stats.pushed, stats.injected)
    return full_mask, weight, count, lab, overflow, residual, comm


@functools.lru_cache(maxsize=32)
def _build_planned_fn(n: int, vps: int, mesh: jax.sharding.Mesh,
                      axes: Tuple[str, ...], plan: RoundPlan):
    fn = partial(_planned_shard_fn, n=n, vps=vps, axes=axes, plan=plan)
    spec = P(axes)
    return jax.jit(compat.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * 4,
        out_specs=(spec, P(), P(), spec, P(), P(), P())))


@functools.lru_cache(maxsize=32)
def _build_planned_batch_fn(n: int, vps: int, mesh: jax.sharding.Mesh,
                            axes: Tuple[str, ...], plan: RoundPlan):
    """The batched planned executor (ISSUE 6): one compiled program
    serving B same-shape graphs per dispatch.

    ``jax.vmap`` of the per-shard planned program over a leading batch
    axis, inside ``shard_map``: the mesh collectives (psum / pmax /
    all_to_all) operate over the *named* axes and batch elementwise
    over the unnamed vmap axis, so B graphs cost one compiled program
    and one collective sequence of B-fold payload.  Inputs are stacked
    ``[B, p * cap]`` edge arrays sharded on dim 1; outputs keep the
    per-request axis — ``mask``/``lab`` are ``[B, p * cap]`` /
    ``[B, p * vps]`` and every scalar (weight, count, **overflow,
    residual**) is a ``[B]`` vector, so one ill-fitting request is
    visible — and replannable — on its own, without poisoning its
    batchmates (``execute_plan_batched``).
    """
    fn = jax.vmap(partial(_planned_shard_fn, n=n, vps=vps, axes=axes,
                          plan=plan))
    spec = P(None, axes)
    rep = P(None)
    return jax.jit(compat.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * 4,
        out_specs=(spec, rep, rep, spec, rep, rep, rep)))


def _planned_segment_shard_fn(u, v, w, eid, lab0=None, mst0=None,
                              dead0=None, settled0=None, *, n: int,
                              vps: int, axes: Tuple[str, ...],
                              plan: RoundPlan, start: int, stop: int):
    """Plan-round segment [start, stop) of the unrolled executor
    (ISSUE 9: checkpointed / resumed planned execution).

    The same straight-line program as ``_planned_shard_fn``, cut at
    static plan-round indices so the host can interleave the certify +
    snapshot barrier between compiled segments, or skip ahead to a
    checkpoint's ``plan_pos`` with a restored carry.  ``start == 0``
    runs the setup phases (preprocessing, ghost fill); ``start > 0``
    takes the carry (lab / mask / dead / settled) instead — the
    checkpointed mask already folds the preprocessing picks in, and
    the ghost tables are rebuilt from the restored labels through the
    existing setup path.  A segment whose first round opens a new
    filter level ignores ``settled0`` (a new weight window revives
    edges, same rule as the driver).

    ``residual`` is charged only for levels whose *final* planned
    round executes inside this segment — earlier segments of a
    mid-level cut leave the judgement to the segment that runs the
    level's sentinel.

    Returns the 7-tuple of ``_planned_shard_fn`` plus the (dead,
    settled) carry the next segment or the checkpoint needs.
    """
    names = tuple(axes)
    valid = jnp.isfinite(w)
    overflow = jnp.int32(0)
    stats = ExchangeStats.zeros()

    if start == 0:
        base = lax.axis_index(names) * vps
        lab = base + jnp.arange(vps, dtype=jnp.int32)
        mst = compat.vary(jnp.zeros(u.shape, bool), names)
        if plan.local_preprocessing:
            lab, pre_mst, dead, ovf, stats, _ = _sharded_preprocess(
                u, v, w, eid, valid, n, vps, plan.cap_prep, names,
                plan.schedule, stats)
            overflow += ovf
        else:
            pre_mst = compat.vary(jnp.zeros(u.shape, bool), names)
            dead = u == v
    else:
        lab, mst, dead = lab0, mst0, dead0
        pre_mst = compat.vary(jnp.zeros(u.shape, bool), names)

    runs_v = None
    if plan.ghost is not None:
        gp = plan.ghost
        gstate, vidx, runs_u, ovf, stats = _ghost_setup(
            u, v, valid, valid & ~dead, lab, None, n, vps, gp.table_u,
            gp.table_v, gp.cap_fill_u, gp.cap_fill_v, gp.cap_subscribe,
            names, plan.schedule, stats, plan.grid_push)
        overflow += ovf
        nu = lax.pmax(jnp.sum(runs_u[0].astype(jnp.int32)), names)
        nv = lax.pmax(jnp.sum(vidx.runs[0].astype(jnp.int32)), names)
        overflow += jnp.maximum(nu - gp.table_u, 0) \
            + jnp.maximum(nv - gp.table_v, 0)
    else:
        gstate = None
        runs_u = run_metadata(u) if (plan.coalesce or plan.src_only) \
            else None
        vidx = _build_v_index(v, valid, n, names) \
            if (plan.coalesce and plan.vsorted_index) else None
        runs_v = run_metadata(v) \
            if (plan.coalesce and not plan.vsorted_index) else None

    residual = jnp.int32(0)
    start_level = plan.rounds[start].level \
        if plan.rounds and start < len(plan.rounds) else 0
    fresh_level = (start == 0 or not plan.rounds
                   or plan.rounds[start].level
                   != plan.rounds[start - 1].level)
    settled = compat.vary(jnp.zeros((vps,), bool), names)
    for lvl, (lo, hi) in enumerate(plan.level_bounds):
        if lvl < start_level:
            continue
        idxs = [i for i, s in enumerate(plan.rounds) if s.level == lvl]
        run = [i for i in idxs if start <= i < stop]
        if not run:
            continue
        live0 = valid
        if len(plan.level_bounds) > 1:
            live0 = valid & (w > jnp.float32(lo)) & (w <= jnp.float32(hi))
        if lvl == start_level and not fresh_level:
            settled = settled0
        else:
            settled = compat.vary(jnp.zeros((vps,), bool), names)
        go = None
        for i in run:
            spec = plan.rounds[i]
            fallback = plan.ghost is not None and not spec.ghost
            coalesce_eff = plan.coalesce or fallback
            vidx_r = vidx if (spec.ghost
                              or (coalesce_eff and vidx is not None)) \
                else None
            lab, mst, dead, gstate, settled, go, o, stats = _round_body(
                u, v, w, eid, live0, lab, mst, dead, runs_u, runs_v,
                vidx_r, gstate, settled, n, vps, names, spec.cap_edge,
                spec.cap_relabel, spec.cap_lookup, spec.cap_contract,
                spec.cap_push, spec.cap_push_col, plan.schedule,
                coalesce_eff, plan.src_only, plan.adaptive_doubling,
                spec.ghost, plan.relabel_skip, plan.pallas_minedges,
                plan.grid_push and spec.ghost, stats)
            overflow += o
        if go is not None and idxs[-1] < stop:
            residual += go.astype(jnp.int32)

    full_mask = mst | pre_mst
    weight = lax.psum(jnp.sum(jnp.where(full_mask, w, 0.0)), names)
    count = lax.psum(jnp.sum(full_mask.astype(jnp.int32)), names)
    comm = CommStats(stats.calls, stats.items, stats.bytes,
                     jnp.int32(stop - start), stats.hits, stats.misses,
                     stats.pushed, stats.injected)
    return (full_mask, weight, count, lab, overflow, residual, comm,
            dead, settled)


@functools.lru_cache(maxsize=64)
def _build_planned_segment_fn(n: int, vps: int, mesh: jax.sharding.Mesh,
                              axes: Tuple[str, ...], plan: RoundPlan,
                              start: int, stop: int):
    fn = partial(_planned_segment_shard_fn, n=n, vps=vps, axes=axes,
                 plan=plan, start=start, stop=stop)
    spec = P(axes)
    nin = 4 if start == 0 else 8
    return jax.jit(compat.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * nin,
        out_specs=(spec, P(), P(), spec, P(), P(), P(), spec, spec)))


@functools.lru_cache(maxsize=32)
def _build_planned_segment_batch_fn(n: int, vps: int,
                                    mesh: jax.sharding.Mesh,
                                    axes: Tuple[str, ...],
                                    plan: RoundPlan, start: int,
                                    stop: int):
    """Vmapped segment executor: B same-shape requests skip ahead to
    one shared ``plan_pos`` with stacked restored carries (the batched
    resume of ``execute_plan_batched``)."""
    fn = jax.vmap(partial(_planned_segment_shard_fn, n=n, vps=vps,
                          axes=axes, plan=plan, start=start, stop=stop))
    spec = P(None, axes)
    rep = P(None)
    nin = 4 if start == 0 else 8
    return jax.jit(compat.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * nin,
        out_specs=(spec, rep, rep, spec, rep, rep, rep, spec, spec)))


# fault injection (comm/faults.py, ISSUE 7) must force a retrace when a
# plan activates/deactivates: every memoized builder of a program that
# routes through the exchanges registers its invalidator here
for _b in (_build_sharded_fn, _build_sharded_prep_fn,
           _build_ghost_setup_fn, _build_sharded_round_fn,
           _build_planned_fn, _build_planned_batch_fn,
           _build_planned_segment_fn, _build_planned_segment_batch_fn):
    faults.register_cache_clear(_b.cache_clear)
del _b


def _replan_with_plan(graph: DistGraph, n: int, mesh: jax.sharding.Mesh,
                      axes: Tuple[str, ...], plan: RoundPlan,
                      round_trace: Optional[List[dict]] = None,
                      ckpt_every: Optional[int] = None,
                      ckpt_out: Optional[List] = None,
                      resume_from: Optional[MSFCheckpoint] = None):
    """One fresh measured pass with the plan's frozen levers — the
    overflow/residual fallback shared by ``distributed_sharded_msf``'s
    plan path, ``execute_plan_batched`` and the serving gateway's
    strict-measured retry rung.  The checkpoint kwargs (ISSUE 9) pass
    through to the shrinking driver, which is how the gateway's ladder
    takes certified snapshots during — and resumes interrupted — rungs."""
    return distributed_sharded_msf(
        graph, n, mesh, algorithm=plan.algorithm, axis_names=axes,
        num_levels=len(plan.level_bounds), schedule=plan.schedule,
        local_preprocessing=plan.local_preprocessing,
        coalesce=plan.coalesce, src_only=plan.src_only,
        adaptive_doubling=plan.adaptive_doubling,
        shrink_capacities=True, ghost_cache=plan.ghost is not None,
        ghost_push=(("grid" if plan.grid_push else "flat")
                    if plan.ghost is not None else None),
        relabel_skip=plan.relabel_skip,
        vsorted_index=plan.vsorted_index,
        pallas_minedges=plan.pallas_minedges, round_trace=round_trace,
        ckpt_every=ckpt_every, ckpt_out=ckpt_out,
        resume_from=resume_from)


def execute_plan_batched(graphs: Sequence[DistGraph], n: int,
                         mesh: jax.sharding.Mesh, plan: RoundPlan, *,
                         axis_names: Optional[Sequence[str]] = None,
                         replan=True,
                         stack: bool = True,
                         verify: bool = False,
                         resume_from: Optional[
                             Sequence[MSFCheckpoint]] = None):
    """Replay one measured ``RoundPlan`` on B same-shape graphs at once.

    The batch is stacked to ``[B, p * cap]`` and served through the
    vmapped planned program (``_build_planned_batch_fn``) in a single
    dispatch.  Per-request overflow / residual accounting keeps the
    never-silent contract *independently per request*: requests the
    plan fits are returned from the batched run as-is; each request the
    plan does not fit is re-solved by its own fresh measured pass
    (``replan=True``, the serving default), the whole call raises
    naming the offending batch indices (``replan=False``), or the bad
    requests come back as ``None`` results for the caller to handle
    (``replan="defer"`` — the gateway's retry ladder, ISSUE 7, which
    must choose between retry, replan, and rejection itself).

    ``verify=True`` (ISSUE 7) self-checks every returned forest
    on-device at O(n/p) cost (``core/verify.py``: edge count = n −
    components, label pointer-chase convergence, psum'd weight
    checksum against the program's own reported scalars).  A forest
    failing verification is treated exactly like an ill-fitting
    request: replanned and re-verified strictly (``replan=True``),
    deferred to ``None`` (``replan="defer"``), or the typed
    ``VerifyFailure`` propagates (``replan=False``).

    Returns ``(results, flagged)``: ``results[i]`` is the engine's
    standard 6-tuple ``(mask, weight, count, labels, overflow, stats)``
    for ``graphs[i]`` (overflow 0 for every request, replanned or not),
    and ``flagged`` is the tuple of batch indices that fell back or
    deferred — the serving gateway's drift signal.

    ``stack=False`` asserts the caller already stacked the arrays
    (``graphs`` is then one ``DistGraph`` of ``[B, p * cap]`` arrays).

    ``resume_from`` (ISSUE 9) is one certified ``MSFCheckpoint`` per
    request, all sharing the same ``plan_pos``: the batch skips ahead
    to that plan round in one vmapped segment dispatch with the
    stacked restored carries, bit-identical to the full batched
    replay.  Checkpoints are *taken* per request via
    ``execute_plan(ckpt_every=...)`` — the batched program has no host
    between rounds to certify at.
    """
    axes = tuple(axis_names or mesh.axis_names)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    vps = vertices_per_shard(n, p)
    if stack:
        for g in graphs:
            _validate_plan_shape(plan, n, p, g.cap_total // p)
        batch_size = len(graphs)
        batched = DistGraph(
            jnp.stack([g.u for g in graphs]),
            jnp.stack([g.v for g in graphs]),
            jnp.stack([g.w for g in graphs]),
            jnp.stack([g.eid for g in graphs]))

        def graph_at(i):
            return graphs[i]
    else:
        batched = graphs
        batch_size = int(batched.u.shape[0])
        _validate_plan_shape(plan, n, p, int(batched.u.shape[1]) // p)

        def graph_at(i):   # only materialized for replanned requests
            return DistGraph(batched.u[i], batched.v[i], batched.w[i],
                             batched.eid[i])
    if resume_from is None:
        fn = _build_planned_batch_fn(n, vps, mesh, axes, plan)
        out = fn(batched.u, batched.v, batched.w, batched.eid)
    else:
        cks = list(resume_from)
        if len(cks) != batch_size or any(c is None for c in cks):
            raise CheckpointError(
                f"batched resume needs one checkpoint per request "
                f"({batch_size}), got {len(cks)} "
                f"({sum(c is None for c in cks)} missing)")
        poss = {c.plan_pos for c in cks}
        if len(poss) != 1 or None in poss:
            raise CheckpointError(
                "batched resume needs every checkpoint at one shared "
                f"plan position (one compiled segment), got {poss}")
        cap_b = int(batched.u.shape[1]) // p
        for c in cks:
            c.validate_for(n, p, cap_b)
        pos = int(cks[0].plan_pos)
        if not 0 < pos <= len(plan.rounds):
            raise CheckpointError(
                f"checkpoint plan_pos={pos} is outside this plan's "
                f"{len(plan.rounds)} rounds — taken against a "
                "different plan")
        fn = _build_planned_segment_batch_fn(n, vps, mesh, axes, plan,
                                             pos, len(plan.rounds))
        out = fn(batched.u, batched.v, batched.w, batched.eid,
                 jnp.stack([jnp.asarray(c.lab) for c in cks]),
                 jnp.stack([jnp.asarray(c.mask) for c in cks]),
                 jnp.stack([jnp.asarray(c.dead) for c in cks]),
                 jnp.stack([jnp.asarray(c.settled) for c in cks]))
    mask, weight, count, lab, ovf, residual, comm = out[:7]
    ovf_h = np.asarray(ovf)
    res_h = np.asarray(residual)
    defer = replan == "defer"
    bad = tuple(int(i) for i in
                np.nonzero((ovf_h != 0) | (res_h != 0))[0])
    if bad and not replan:
        raise RuntimeError(
            f"plan replay does not fit batch requests {list(bad)} "
            f"(overflow={[int(ovf_h[i]) for i in bad]}, residual="
            f"{[int(res_h[i]) for i in bad]}); pad the plan, re-measure "
            "with plan_sharded_msf, or allow replan=True")
    results = []
    for i in range(batch_size):
        if i in bad:
            if defer:
                results.append(None)
            else:
                # this request alone falls back to one fresh measured
                # pass with the plan's frozen levers; batchmates keep
                # their batched results untouched
                results.append(_replan_with_plan(graph_at(i), n, mesh,
                                                 axes, plan))
        else:
            results.append((mask[i], weight[i], count[i], lab[i],
                            ovf[i], CommStats(*(f[i] for f in comm))))
    if verify:
        from repro.core.verify import VerifyFailure, verify_forest
        for i, res in enumerate(results):
            if res is None:
                continue
            try:
                verify_forest(graph_at(i), n, mesh, res[0], res[3],
                              axis_names=axes,
                              expected_weight=float(res[1]),
                              expected_count=int(res[2]))
            except VerifyFailure:
                if defer:
                    results[i] = None
                    if i not in bad:
                        bad = bad + (i,)
                elif replan and i not in bad:
                    # one strict rung: replan, re-verify, then propagate
                    g = graph_at(i)
                    r2 = _replan_with_plan(g, n, mesh, axes, plan)
                    verify_forest(g, n, mesh, r2[0], r2[3],
                                  axis_names=axes,
                                  expected_weight=float(r2[1]),
                                  expected_count=int(r2[2]))
                    results[i] = r2
                    bad = bad + (i,)
                else:
                    raise
    return results, bad


def _ghost_push_mode(ghost_cache: bool, mode: Optional[str],
                     axis_sizes: Tuple[int, ...],
                     limit: Optional[int]) -> Tuple[bool, bool]:
    """Select the ghost push implementation for this mesh (ISSUE 10).

    Returns ``(ghost_on, grid)`` down the fallback ladder:

      * **flat** (single whole-mesh bitmask, ``scatter_updates``) when
        the shard count fits one int32 mask — ``p <= min(limit, 31)``;
      * **grid** (per-axis mask pair, ``scatter_updates_grid``) when it
        does not but the mesh factors into exactly two axes of at most
        ``min(limit, 31)`` shards each — up to 961 shards;
      * **off** (exact coalesced lookups) beyond both.

    ``limit`` is the user's ``ghost_shard_limit`` (None → 31); it caps
    the *per-mask* width on both rungs, which is what makes the ladder
    testable on a small mesh (p=8 on (4, 2): limit 31 → flat, limit 7 →
    grid, limit 1 → off).  An explicit ``mode`` ("flat" / "grid") skips
    the auto ladder and raises loudly when the mesh cannot honor it —
    never a silent downgrade.
    """
    p = 1
    for s in axis_sizes:
        p *= s
    if not ghost_cache:
        return False, False
    lim = MAX_GHOST_SHARDS if limit is None else int(limit)
    width = min(lim, MAX_GHOST_SHARDS)
    if mode == "flat":
        if p > MAX_GHOST_SHARDS:
            raise ValueError(
                f"ghost_push='flat' needs p <= {MAX_GHOST_SHARDS} "
                f"(int32 subscriber bitmask), got p={p}")
        return True, False
    if mode == "grid":
        if len(axis_sizes) != 2:
            raise ValueError(
                "ghost_push='grid' needs a 2-axis (row, col) mesh, got "
                f"{len(axis_sizes)} axes {tuple(axis_sizes)}")
        if max(axis_sizes) > MAX_GHOST_SHARDS:
            raise ValueError(
                f"ghost_push='grid' needs every mesh axis <= "
                f"{MAX_GHOST_SHARDS}, got {tuple(axis_sizes)}")
        return True, True
    if mode is not None:
        raise ValueError(
            f"unknown ghost_push mode {mode!r}; one of None (auto), "
            "'flat', 'grid'")
    if p <= width:
        return True, False
    if len(axis_sizes) == 2 and max(axis_sizes) <= width:
        return True, True
    return False, False


def _validate_plan_shape(plan: RoundPlan, n: int, p: int,
                         cap: int) -> None:
    plan.validate()
    if (plan.n, plan.num_shards, plan.cap_per_shard) != (n, p, cap):
        raise ValueError(
            f"plan was measured for n={plan.n}, p={plan.num_shards}, "
            f"cap/shard={plan.cap_per_shard} but this solve has n={n}, "
            f"p={p}, cap/shard={cap}; plans only transfer across "
            "graphs built at the same shape")


def plan_sharded_msf(graph: DistGraph, n: int, mesh: jax.sharding.Mesh,
                     *, algorithm: str = "boruvka",
                     axis_names: Optional[Sequence[str]] = None,
                     num_levels: int = 4,
                     max_rounds: Optional[int] = None,
                     edge_capacity: Optional[int] = None,
                     label_capacity: Optional[int] = None,
                     lookup_capacity: Optional[int] = None,
                     schedule: str = "grid",
                     local_preprocessing: bool = True,
                     coalesce: bool = True, src_only: bool = True,
                     adaptive_doubling: bool = True,
                     ghost_cache: bool = True, relabel_skip: bool = True,
                     vsorted_index: bool = True,
                     pallas_minedges: bool = False,
                     ghost_push: Optional[str] = None,
                     ghost_shard_limit: Optional[int] = None,
                     push_capacity: Optional[int] = None,
                     round_trace: Optional[List[dict]] = None
                     ) -> RoundPlan:
    """Measure a ``RoundPlan`` for ``graph`` (one host-interleaved pass).

    Runs the shrinking-capacity driver as the measurement backend and
    freezes the schedule it chose — per-round exchange capacities
    (already snapped to the ``shrink_schedule`` ladder, so plans
    transfer across structurally similar graphs), the one-off
    preprocessing / ghost-setup capacities, the filter-level weight
    windows and one trailing sentinel round per level that ended on a
    zero host bound.  The returned plan drives the Python-unrolled
    executor: ``distributed_sharded_msf(..., plan=plan)`` (works under
    AOT tracing — ``make_sharded_mst_step(plan=...)``), ``plan.pad``
    for serving headroom, ``plan.to_json`` for persistence.

    Raises on nonzero measurement overflow (user-undersized explicit
    capacities): a plan recorded off a lossy pass would be garbage.

    ``round_trace`` passes through to the driver, so one call yields
    both the plan and the measured per-round comm table.
    """
    obs.begin()
    axes = tuple(axis_names or mesh.axis_names)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    vps = vertices_per_shard(n, p)
    cap = graph.cap_total // p
    if isinstance(graph.u, jax.core.Tracer):
        raise ValueError("plan_sharded_msf measures exact host bounds "
                         "and needs a concrete graph, not tracers")
    ghost_cache, grid_push = _ghost_push_mode(
        ghost_cache, ghost_push,
        tuple(mesh.shape[a] for a in axes), ghost_shard_limit)
    ce = int(cap if edge_capacity is None else edge_capacity)
    cl = int(vps if label_capacity is None else label_capacity)
    if lookup_capacity is None:
        with obs.span("driver.lookup_bound"):
            lk = default_lookup_capacity(
                graph, p, n, vsorted=vsorted_index or ghost_cache) \
                if (coalesce or ghost_cache) else ce
    else:
        lk = int(lookup_capacity)
    rec: dict = {}
    res = _shrinking_capacity_msf(
        graph, n, mesh, axes, algorithm, num_levels, max_rounds, ce, cl,
        lk, schedule, local_preprocessing, coalesce, src_only,
        adaptive_doubling, ghost_cache, relabel_skip, vsorted_index,
        push_capacity, round_trace, plan_out=rec,
        pallas_minedges=pallas_minedges, grid_push=grid_push)
    if int(res[4]):
        raise RuntimeError(
            f"measurement pass overflowed ({int(res[4])} items): a plan "
            "recorded off a lossy pass would be unreliable — retry with "
            "larger explicit capacities (or the exact defaults)")
    return RoundPlan(
        n=n, num_shards=p, cap_per_shard=cap, algorithm=algorithm,
        schedule=schedule, local_preprocessing=local_preprocessing,
        coalesce=coalesce, src_only=src_only,
        adaptive_doubling=adaptive_doubling, relabel_skip=relabel_skip,
        vsorted_index=vsorted_index, cap_prep=cl, edge_capacity_full=ce,
        label_capacity_full=cl, lookup_capacity_full=lk,
        ghost=rec.get("ghost"),
        level_bounds=tuple(rec["level_bounds"]),
        rounds=tuple(rec["rounds"]),
        pallas_minedges=pallas_minedges,
        grid_push=grid_push and rec.get("ghost") is not None).validate()


def execute_plan(graph: DistGraph, n: int, mesh: jax.sharding.Mesh,
                 plan: RoundPlan, *,
                 axis_names: Optional[Sequence[str]] = None,
                 replan: bool = True,
                 round_trace: Optional[List[dict]] = None,
                 verify: bool = False,
                 ckpt_every: Optional[int] = None,
                 ckpt_out: Optional[List] = None,
                 resume_from: Optional[MSFCheckpoint] = None):
    """Replay a measured ``RoundPlan`` on a same-shape graph.

    Alias for ``distributed_sharded_msf(graph, n, mesh, plan=plan)``:
    runs the compiled Python-unrolled program and — if the plan does
    not fit this graph (overflow, or residual rounds after a level's
    last planned round) — falls back to one fresh measured pass with
    the plan's levers (``replan=True``, the serving default) or raises
    (``replan=False``, the strict mode tests pin replay exactness
    with).  Never returns an unreliable result silently.

    ``round_trace`` is **replan-only** here: the unrolled program has
    no host between rounds to tabulate, so a fitting replay leaves the
    list empty — per-round numbers for a plan come from the plan
    itself (``launch/roofline.py: plan_summary``) or from the
    measurement pass (``plan_sharded_msf(round_trace=...)``).

    ``verify=True`` (ISSUE 7) self-checks the returned forest on-device
    (``core/verify.py``) against the structural MSF invariants and the
    program's own reported scalars, raising a typed ``VerifyFailure``
    instead of returning a silently wrong forest.  Concrete inputs
    only — under tracing the check is skipped (the AOT contract folds
    every hazard into ``overflow`` instead).

    Checkpointing (ISSUE 9): ``ckpt_every=k`` with ``ckpt_out`` cuts
    the unrolled program at plan-round cadence boundaries
    (``_planned_segment_shard_fn``) and runs the certify + snapshot
    barrier between compiled segments; ``resume_from=ck`` skips ahead
    to the checkpoint's ``plan_pos`` with the restored carry.  The
    interrupted-then-resumed result is bit-identical to the plain
    one-program replay.  Concrete inputs only (the barrier is a host
    step); plain calls keep the single-program fast path.
    """
    if ckpt_every is None and ckpt_out is None and resume_from is None:
        out = distributed_sharded_msf(graph, n, mesh, plan=plan,
                                      axis_names=axis_names,
                                      replan=replan,
                                      round_trace=round_trace)
        if verify and not isinstance(graph.u, jax.core.Tracer):
            from repro.core.verify import verify_forest
            verify_forest(graph, n, mesh, out[0], out[3],
                          axis_names=axis_names,
                          expected_weight=float(out[1]),
                          expected_count=int(out[2]))
        return out
    if isinstance(graph.u, jax.core.Tracer):
        raise ValueError(
            "checkpointed plan execution interleaves a host barrier "
            "between compiled segments and needs concrete inputs")
    axes = tuple(axis_names or mesh.axis_names)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    vps = vertices_per_shard(n, p)
    cap = graph.cap_total // p
    _validate_plan_shape(plan, n, p, cap)
    R = len(plan.rounds)
    start = 0
    carry = None
    acc = np.zeros(_STAT_FIELDS, np.float64)
    total_ovf = total_res = 0
    if resume_from is not None:
        ck = resume_from.validate_for(n, p, cap)
        if ck.plan_pos is None:
            raise CheckpointError(
                "this checkpoint was taken by the host driver (no plan "
                "position); resume it via distributed_sharded_msf("
                "resume_from=...) instead")
        if not 0 < ck.plan_pos <= R:
            raise CheckpointError(
                f"checkpoint plan_pos={ck.plan_pos} is outside this "
                f"plan's {R} rounds — it was taken against a different "
                "plan")
        start = int(ck.plan_pos)
        carry = (jnp.asarray(ck.lab), jnp.asarray(ck.mask),
                 jnp.asarray(ck.dead), jnp.asarray(ck.settled))
        acc += ck.stats_acc
    stops = []
    if ckpt_every:
        k = int(ckpt_every)
        stops = list(range((start // k + 1) * k, R, k))
    stops.append(R)
    out = None
    for stop_i in stops:
        if stop_i <= start:
            continue
        fn = _build_planned_segment_fn(n, vps, mesh, axes, plan, start,
                                       stop_i)
        args = (graph.u, graph.v, graph.w, graph.eid)
        out = fn(*args) if start == 0 else fn(*args, *carry)
        (mask, weight, count, lab, ovf, residual, comm, dead,
         settled) = out
        total_ovf += int(ovf)
        total_res += int(residual)
        acc += [float(comm[0]), float(comm[1]), float(comm[2]), 0.0,
                float(comm[4]), float(comm[5]), float(comm[6]),
                float(comm[7])]
        if stop_i < R and not total_ovf:
            lvl_next = plan.rounds[stop_i].level
            fresh = lvl_next != plan.rounds[stop_i - 1].level
            settled_h = np.zeros(p * vps, bool) if fresh \
                else np.asarray(settled)
            r_next = sum(1 for j in range(stop_i)
                         if plan.rounds[j].level == lvl_next)
            ck2 = _certified_checkpoint(
                graph, n, mesh, axes, p, cap, plan.algorithm,
                plan.level_bounds, stop_i, lvl_next, r_next, stop_i,
                lab, np.asarray(mask), np.asarray(dead), settled_h,
                plan.ghost is not None, acc)
            if ck2 is not None and ckpt_out is not None:
                ckpt_out.append(ck2)
        carry = (lab, mask, dead, settled)
        start = stop_i
    if out is None:  # resume_from at plan end: nothing left to run
        mask, weight, count, lab = (jnp.asarray(ck.mask),
                                    None, None, jnp.asarray(ck.lab))
        w_h = np.asarray(graph.w)
        m_h = np.asarray(ck.mask)
        weight = np.float32(np.sum(w_h[m_h], dtype=np.float64))
        count = np.int32(int(m_h.sum()))
    comm_total = CommStats(np.int32(acc[0]), np.float32(acc[1]),
                           np.float32(acc[2]),
                           np.int32(plan.num_rounds),
                           np.float32(acc[4]), np.float32(acc[5]),
                           np.float32(acc[6]), np.float32(acc[7]))
    if total_ovf or total_res:
        if not replan:
            raise RuntimeError(
                f"plan replay does not fit this graph (overflow="
                f"{total_ovf}, residual levels={total_res}); pad the "
                "plan, re-measure with plan_sharded_msf, or allow "
                "replan=True")
        return _replan_with_plan(graph, n, mesh, axes, plan,
                                 round_trace=round_trace,
                                 ckpt_every=ckpt_every,
                                 ckpt_out=ckpt_out)
    result = (mask, weight, count, lab, np.int32(total_ovf), comm_total)
    if verify:
        from repro.core.verify import verify_forest
        verify_forest(graph, n, mesh, result[0], result[3],
                      axis_names=axes,
                      expected_weight=float(result[1]),
                      expected_count=int(result[2]))
    return result


def vertices_per_shard(n: int, num_shards: int) -> int:
    return max(1, -(-n // num_shards))


def default_lookup_capacity(graph: DistGraph, num_shards: int, n: int,
                            alive: Optional[np.ndarray] = None,
                            vsorted: bool = True,
                            vindex: Optional[Tuple[np.ndarray,
                                                   np.ndarray]] = None
                            ) -> int:
    """Exact-by-construction capacity for the coalesced endpoint lookups.

    One host-side pass over the (already host-built) edge arrays counts,
    per (shard, owner) pair, the coalesced requests each endpoint column
    can send: the u column's contiguous equal-value runs in slot order
    (u is the lexicographic sort's major key), and — since ISSUE 4 —
    the v column's runs through the **v-sorted secondary index**
    (``_host_v_perm``), i.e. one request per distinct v per shard, which
    is what makes high-locality graphs' lookup buffers shrink on the v
    side too (the rgg2d gap PR 3 left open).  Typically
    ~edges/(shard·avg_degree) instead of edges/shard.

    With ``alive`` (a [p * cap] bool mask of slots still live) only runs
    containing at least one live slot count — exactly the runs the
    engine's coalesced lookup will send a request for, so the bound
    stays exact.  The shrinking-capacity driver calls this once per
    round with the current dead-edge mask folded in.
    ``vsorted=False`` bounds the v side by its slot-order runs instead —
    the PR 3 comparator path (``vsorted_index=False``).  ``vindex``
    optionally supplies a precomputed ``_host_v_perm`` result — the
    per-round caller (the shrinking driver) computes it once per solve
    instead of re-sorting the static v column every round.
    """
    vps = vertices_per_shard(n, num_shards)
    cap = graph.cap_total // num_shards
    shard = np.repeat(np.arange(num_shards), cap)
    live = None if alive is None else np.asarray(alive)
    u_h = np.asarray(graph.u)
    head, rid = _host_run_heads(u_h, num_shards)
    send = head
    if live is not None:
        run_live = np.bincount(rid[live],
                               minlength=int(rid[-1]) + 1) > 0
        send = head & run_live[rid]
    mx = max(1, _per_pair_max(shard[send], u_h[send] // vps, num_shards))
    v_h = np.asarray(graph.v)
    if not vsorted:
        head_v, rid_v = _host_run_heads(v_h, num_shards)
        send_v = head_v
        if live is not None:
            run_live_v = np.bincount(rid_v[live],
                                     minlength=int(rid_v[-1]) + 1) > 0
            send_v = head_v & run_live_v[rid_v]
        return max(mx, _per_pair_max(shard[send_v], v_h[send_v] // vps,
                                     num_shards))
    if vindex is None:
        valid_h = np.isfinite(np.asarray(graph.w))
        perm, skey = _host_v_perm(v_h, valid_h, n, num_shards)
    else:
        perm, skey = vindex
    head_v, rid_v = _host_run_heads(skey, num_shards)
    send_v = head_v & (skey < n)
    if live is not None:
        live_p = np.take_along_axis(live.reshape(num_shards, cap),
                                    perm.reshape(num_shards, cap),
                                    axis=1).reshape(-1)
        run_live_v = np.bincount(rid_v[live_p],
                                 minlength=int(rid_v[-1]) + 1) > 0
        send_v = send_v & run_live_v[rid_v]
    mx = max(mx, _per_pair_max(shard[send_v],
                               (skey[send_v] // vps).astype(np.int64),
                               num_shards))
    return mx


def distributed_sharded_msf(graph: DistGraph, n: int,
                            mesh: jax.sharding.Mesh, *,
                            algorithm: str = "boruvka",
                            axis_names: Optional[Sequence[str]] = None,
                            num_levels: int = 4,
                            max_rounds: Optional[int] = None,
                            edge_capacity: Optional[int] = None,
                            label_capacity: Optional[int] = None,
                            lookup_capacity: Optional[int] = None,
                            schedule: str = "grid",
                            local_preprocessing: bool = True,
                            coalesce: bool = True,
                            src_only: bool = True,
                            adaptive_doubling: bool = True,
                            shrink_capacities: bool = True,
                            ghost_cache: bool = True,
                            relabel_skip: bool = True,
                            vsorted_index: bool = True,
                            pallas_minedges: bool = False,
                            ghost_push: Optional[str] = None,
                            push_capacity: Optional[int] = None,
                            round_trace: Optional[List[dict]] = None,
                            plan: Optional[RoundPlan] = None,
                            replan: bool = True,
                            ghost_shard_limit: Optional[int] = None,
                            ckpt_every: Optional[int] = None,
                            ckpt_out: Optional[List] = None,
                            resume_from: Optional[MSFCheckpoint] = None):
    """Run the sharded-label distributed MSF on a mesh.

    Returns (mask, weight, count, labels, overflow, stats):
      * ``mask`` is aligned with ``graph`` slots, exactly one directed
        copy per MSF edge (the canonical u < v copy when
        ``src_only=False``);
      * ``labels`` is the *sharded* label vector laid out shard-major
        ([p * vertices_per_shard], slice [:n] for the per-vertex view);
      * ``overflow`` counts exchange items that exceeded capacity summed
        over all rounds — results are exact iff it is 0 (guaranteed with
        the default capacities); callers passing smaller capacities must
        retry larger on a positive count;
      * ``stats`` is a ``CommStats`` (all-to-all invocations, routed
        items, buffer bytes, rounds, plus the ghost cache's
        hits / misses / pushed triple) — the honest comm metric the
        optimization flags move (benchmarks/sharded_scaling.py).

    ``shrink_capacities=True`` (default) runs the host-orchestrated
    per-round capacity schedule: each round's MINEDGES / lookup /
    contract / RELABEL / push exchanges are sized from host bounds on
    the measured dead-edge mask, snapped to the geometric ladder of
    ``core/distributed.py: shrink_schedule`` — bit-identical results,
    geometrically decaying buffer bytes.  ``round_trace`` (a caller
    list) then receives one dict per round with the chosen capacities
    and measured comm deltas.  Under AOT lowering (tracer inputs,
    ``make_sharded_mst_step``) and with ``shrink_capacities=False`` the
    fused single-program engine with flat capacities runs instead.

    ``ghost_cache=True`` (default, ISSUE 4) keeps per-shard ghost
    tables of remote endpoint labels: one coalesced fill at setup
    (through the v-sorted secondary index, so both endpoint columns
    coalesce to one request per distinct vertex), local reads every
    round, and a dirty-label push from the owners after each
    contraction — steady-state lookup traffic is O(Δlabels).
    ``ghost_push`` selects the push implementation (ISSUE 10): None
    (default) walks the auto ladder — **flat** single-bitmask
    ``scatter_updates`` up to ``MAX_GHOST_SHARDS`` (31) shards, then
    the **two-level grid** ``scatter_updates_grid`` on 2-axis meshes
    whose axes each fit a mask (up to 961 shards, O(√p) fan-out), then
    cache off; ``"flat"``/``"grid"`` pin one rung and raise when the
    mesh cannot honor it.  ``push_capacity`` pins the push exchange
    (diagnostics): the shrinking driver falls back to exact coalesced
    lookups when the pinned value cannot hold a round's dirty bound,
    the fused engine reports push overflow.  ``relabel_skip=True``
    stops settled vertices (their component chose no edge — final
    forever) from re-requesting in RELABEL.  ``vsorted_index=False``
    restores the slot-order v coalescing of PR 3 (the measured
    comparator in benchmarks/sharded_scaling.py; no effect with the
    ghost cache on, which always builds the sorted index).

    ``pallas_minedges=True`` (ISSUE 8) routes both MINEDGES reductions
    — the pre-routing per-run combine and the owner-side scatter-min —
    through the fused ``kernels/segmin`` Pallas kernel
    (``owner_scatter_min``: compiled on TPU, interpreted on the CPU via
    ``default_interpret``) instead of the jnp scatter path; results are
    bit-identical (tests/test_kernels_fuzz.py pins the kernel, the
    equivalence matrix pins the engine) and the jnp path stays the
    measured comparator (benchmarks/kernels_bench.py).

    ``plan`` (ISSUE 5) replays a measured ``RoundPlan`` instead: the
    schedule's per-round capacities become static arguments of one
    Python-unrolled program that jits — and, uniquely among the
    shrinking paths, **AOT-lowers** (tracer inputs are fine).  The
    plan's frozen levers override this call's lever flags.  A plan that
    does not fit the graph is never silent: with concrete inputs the
    call replans (one fresh measured pass; ``replan=False`` raises
    instead), under tracing the residual-round count is folded into the
    returned ``overflow``.  See ``plan_sharded_msf`` / ``execute_plan``
    / ``core/plan.py``.

    ``ghost_shard_limit`` (tests/diagnostics) overrides the
    ``MAX_GHOST_SHARDS`` per-mask width on both ladder rungs, so the
    whole flat → grid → off ladder is exercisable on small meshes
    (p=8 on a (4, 2) mesh: limit 31 → flat, 7 → grid, 1 → off).

    Checkpointing (ISSUE 9, shrinking-capacity path only):
    ``ckpt_every=k`` with ``ckpt_out`` (a caller list) makes the host
    driver run the ``core/verify.py`` invariant barrier every k
    executed rounds and append a certified ``MSFCheckpoint`` on a pass.
    ``resume_from=ck`` re-enters at the snapshot's (level, round):
    the resumed run is **bit-identical** to the uninterrupted one on
    the same mesh, and a ``ck.remap(...)``'d checkpoint restores onto
    a different shard count (elastic restore — pass the re-partitioned
    graph).  The fused and planned paths reject these kwargs loudly;
    checkpointed plan replay lives in ``execute_plan``.

    The flags default to the optimized engine; passing
    ``local_preprocessing=False, coalesce=False, src_only=False,
    adaptive_doubling=False, shrink_capacities=False, ghost_cache=False,
    relabel_skip=False`` reproduces the PR 1 baseline exactly, and
    additionally ``ghost_cache=False, vsorted_index=False`` on top of
    the defaults reproduces the PR 3 optimized engine.
    """
    obs.begin()
    axes = tuple(axis_names or mesh.axis_names)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    vps = vertices_per_shard(n, p)
    cap = graph.cap_total // p
    wants_ckpt = (ckpt_every is not None or ckpt_out is not None
                  or resume_from is not None)
    if plan is not None:
        if wants_ckpt:
            raise ValueError(
                "checkpointing a plan replay goes through execute_plan("
                "ckpt_every=..., resume_from=...), which segments the "
                "unrolled program at cadence boundaries")
        _validate_plan_shape(plan, n, p, cap)
        if plan.grid_push and len(axes) != 2:
            raise ValueError(
                "plan was measured with the two-level grid push and "
                f"needs a 2-axis (row, col) mesh, got axes={axes}")
        fn = _build_planned_fn(n, vps, mesh, axes, plan)
        out = fn(graph.u, graph.v, graph.w, graph.eid)
        mask, weight, count, lab, ovf, residual, comm = out
        if isinstance(graph.u, jax.core.Tracer):
            # AOT lowering: no host to replan on — fold the residual
            # signal into overflow (results exact iff 0, the standard
            # contract) and keep the engine's 6-tuple arity
            return mask, weight, count, lab, ovf + residual, comm
        if int(ovf) == 0 and int(residual) == 0:
            return mask, weight, count, lab, ovf, comm
        if not replan:
            raise RuntimeError(
                f"plan replay does not fit this graph (overflow="
                f"{int(ovf)}, residual levels={int(residual)}); pad the "
                "plan, re-measure with plan_sharded_msf, or allow "
                "replan=True")
        # overflow -> replan fallback: one fresh measured pass with the
        # plan's frozen levers — never a silently unreliable result
        return _replan_with_plan(graph, n, mesh, axes, plan,
                                 round_trace=round_trace)
    ghost_cache, grid_push = _ghost_push_mode(
        ghost_cache, ghost_push,
        tuple(mesh.shape[a] for a in axes), ghost_shard_limit)
    # is-None (not falsy) checks: an explicit 0 must be honored — it
    # yields all-overflow results, which the overflow count reports
    ce = int(cap if edge_capacity is None else edge_capacity)
    cl = int(vps if label_capacity is None else label_capacity)
    # the exact host-side bounds need concrete edge arrays; under AOT
    # lowering (make_sharded_mst_step) fall back to the safe flat bound
    concrete = not isinstance(graph.u, jax.core.Tracer)
    if shrink_capacities and not concrete:
        # no longer a docstring-only caveat (ISSUE 5): the host loop
        # cannot run on tracers, so say so — a RoundPlan is the way to
        # keep the schedule under AOT
        warnings.warn(
            "shrink_capacities is ignored under tracing (host bounds "
            "need concrete inputs): lowering the fused flat-capacity "
            "engine; pass plan=plan_sharded_msf(...) to AOT-lower the "
            "shrinking schedule", stacklevel=2)
    if lookup_capacity is None:
        with obs.span("driver.lookup_bound"):
            lk = default_lookup_capacity(
                graph, p, n, vsorted=vsorted_index or ghost_cache) \
                if ((coalesce or ghost_cache) and concrete) else ce
    else:
        lk = int(lookup_capacity)
    if shrink_capacities and concrete:
        return _shrinking_capacity_msf(
            graph, n, mesh, axes, algorithm, num_levels, max_rounds, ce,
            cl, lk, schedule, local_preprocessing, coalesce, src_only,
            adaptive_doubling, ghost_cache, relabel_skip, vsorted_index,
            push_capacity, round_trace, pallas_minedges=pallas_minedges,
            grid_push=grid_push, ckpt_every=ckpt_every,
            ckpt_out=ckpt_out, resume_from=resume_from)
    if wants_ckpt:
        raise ValueError(
            "checkpointing needs the host-driven shrinking-capacity "
            "path (shrink_capacities=True, concrete inputs): the fused "
            "single-program engine has no round boundary to snapshot at")
    cp = int(vps if push_capacity is None else push_capacity)
    # fused path: the deputy hop has no host bound, so take the safe
    # worst case — a deputy relays at most one full hop-1 buffer per
    # source column (overflow still reported, like every flat capacity)
    cpc = cp * mesh.shape[axes[1]] if grid_push else 0
    shard_fn = _build_sharded_fn(n, vps, mesh, axes, algorithm, num_levels,
                                 max_rounds, ce, cl, lk, cp, cpc, schedule,
                                 local_preprocessing, coalesce, src_only,
                                 adaptive_doubling, ghost_cache,
                                 relabel_skip, vsorted_index,
                                 pallas_minedges, grid_push)
    return shard_fn(graph.u, graph.v, graph.w, graph.eid)


def make_sharded_mst_step(n: int, cap_total: int, mesh: jax.sharding.Mesh,
                          algorithm: str = "boruvka",
                          plan: Optional[RoundPlan] = None, **kw):
    """AOT-lowerable sharded MSF step (dry-run/roofline harness parity).

    With ``plan`` (a ``RoundPlan`` from ``plan_sharded_msf`` or
    ``core/plan.py: synthetic_plan``) the step lowers the
    **Python-unrolled shrinking-schedule program**: per-round measured
    capacities as static arguments, one compiled artifact for the whole
    solve — the serving-replay path, costable by dry-run/roofline
    without running.  The plan's frozen levers override ``algorithm``
    and the lever kwargs; residual-round signals fold into the returned
    ``overflow`` (exact iff 0, the standard contract).

    Without a plan, traced inputs cannot drive the host-orchestrated
    shrinking schedule, so the step lowers the fused flat-capacity
    engine.  Passing ``shrink_capacities=True`` explicitly here is
    therefore an error (it used to be silently ignored); omitting it
    warns once and lowers flat — pass ``shrink_capacities=False`` to
    opt into the flat engine silently.
    """
    if plan is not None:
        p = 1
        for a in tuple(kw.get("axis_names") or mesh.axis_names):
            p *= mesh.shape[a]
        if (cap_total != plan.cap_per_shard * p or n != plan.n
                or p != plan.num_shards):
            raise ValueError(
                f"plan shape (n={plan.n}, p={plan.num_shards}, "
                f"cap/shard={plan.cap_per_shard}) does not match the "
                f"step shape (n={n}, p={p}, "
                f"cap/shard={cap_total // max(p, 1)})")

        def step(u, v, w, eid):
            g = DistGraph(u, v, w, eid)
            return distributed_sharded_msf(
                g, n, mesh, plan=plan,
                axis_names=kw.get("axis_names"))
    else:
        if kw.get("shrink_capacities"):
            raise ValueError(
                "shrink_capacities=True cannot drive the host-"
                "orchestrated schedule under AOT tracing; measure a "
                "RoundPlan once (plan_sharded_msf) and pass plan=..., "
                "or request the flat-capacity engine explicitly with "
                "shrink_capacities=False")
        if "shrink_capacities" not in kw:
            warnings.warn(
                "make_sharded_mst_step without a plan lowers the fused "
                "flat-capacity engine (worst-case buffers every round); "
                "pass plan=plan_sharded_msf(...) to AOT-lower the "
                "shrinking schedule, or shrink_capacities=False to "
                "silence this", stacklevel=2)
            kw = dict(kw, shrink_capacities=False)

        def step(u, v, w, eid):
            g = DistGraph(u, v, w, eid)
            return distributed_sharded_msf(g, n, mesh,
                                           algorithm=algorithm, **kw)

    specs = (
        jax.ShapeDtypeStruct((cap_total,), jnp.int32),
        jax.ShapeDtypeStruct((cap_total,), jnp.int32),
        jax.ShapeDtypeStruct((cap_total,), jnp.float32),
        jax.ShapeDtypeStruct((cap_total,), jnp.int32),
    )
    return step, specs
