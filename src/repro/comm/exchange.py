"""Capacity-bounded sparse all-to-all (the paper's bulk request/reply).

The paper's algorithms batch arbitrary point-to-point messages into sparse
``MPI_Alltoallv`` exchanges.  XLA programs need static shapes, so the
TPU-native equivalent is the *capacity-bounded routed exchange* — the same
discipline MoE dispatch uses: a [p, capacity, ...] send buffer per device,
one (optionally two-level, Section VI-A) all-to-all, and an explicit
overflow count instead of variable message sizes.  Overflow never corrupts
results: overflowing items are reported back to the caller (``sent_ok``)
and the dynamic engines retry at a higher capacity.

Primitives:
  * ``routed_exchange``  — deliver items to destination shards.
  * ``request_reply``    — full round trip: route requests to their home
    shard, apply a local answer function, route answers back to the
    requesting slots (the paper's EXCHANGELABELS pattern).
  * ``scatter_updates``  — push-style multicast: deliver item ``i`` to
    every shard whose bit is set in ``dest_mask[i]`` (the ghost-vertex
    dirty-label push of the sharded MST engine: an owner ships a changed
    label to every subscriber shard in one exchange, no request leg).
  * ``scatter_updates_grid`` — the two-level multicast (Section VI-A
    applied to the push): subscriptions are a *pair* of per-axis
    bitmasks on a (row, col) mesh, and each item travels two hops —
    along the owner's row to one deputy per subscribing column, then
    down each deputy's column to the subscribing rows — so the copy
    matrix shrinks from [L, p] to [L, sqrt(p)] per hop and the fan-out
    from O(p) to O(sqrt(p)), lifting the flat primitive's 31-shard cap
    to 31 x 31 = 961.

Used by: distributed MST (ghost-label exchange, redistribution) and the
MoE layers (token->expert dispatch) — one primitive, two workloads.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import compat, obs
from repro.comm import faults
from repro.comm.grid_alltoall import all_to_all_nd


class ExchangeStats(NamedTuple):
    """Comm accumulator for the routed exchanges (the honest perf metric:
    on one host, wall time over virtual devices is noise — counting the
    all-to-alls and the routed volume is what separates engine variants;
    benchmarks/sharded_scaling.py reports these, and the per-round deltas
    drive the sharded engine's shrinking capacity schedule trace).

    All four are device-invariant scalars, safe to carry through
    shard_map loops and to return with out_spec P().  Field-by-field,
    with the units the benchmarks report:

      * ``calls`` — int32 count of ``lax.all_to_all`` **invocations**.
        One logical exchange of a k-array payload costs k + 1 buffer
        all-to-alls (the +1 is the validity mask); a ``reply`` costs one
        per answer array.  Grid schedules multiply by the hop count (one
        invocation per mesh axis), matching what the interconnect
        actually executes.  Unit: invocations, NOT items or bytes.
      * ``items`` — float32 count of payload **items** accepted into
        send buffers, psum'd over devices (a k-array payload item counts
        once, not k times; ``reply`` counts every occupied receive
        slot).  This is what request coalescing and dead-edge retirement
        shrink.  Unit: routed items, independent of per-item width.
      * ``bytes`` — float32 **capacity-padded buffer bytes** shipped per
        invocation: every [p, capacity, ...] send buffer contributes its
        full static size (validity mask included, grid hop multiplier
        applied) whether or not the slots are occupied.  This is the
        honest memory/wire cost of a static-shape exchange and is what a
        smaller ``capacity`` shrinks even when ``items`` is unchanged.
        Unit: bytes.  float32 because int32 overflows at benchmark size.
      * ``slots`` — float32 count of **buffer slots** allocated across
        calls: one logical exchange (or reply) adds ``p * capacity``
        per hop, with no payload-width multiplier.  Request/reply legs
        ship one pre-packed buffer end to end, so their hop count is
        always 1 logical allocation (``routed_exchange`` books
        ``p * capacity`` once regardless of schedule); the *multicast*
        primitives re-admit items at every hop — ``scatter_updates``
        books ``p * capacity * hops`` and ``scatter_updates_grid``
        books its two legs distinctly (``C * cap_row + R * cap_col``),
        which is exactly the O(sqrt(p))-vs-O(p) fan-out the grid push
        exists to shrink.  This is the capacity-per-call plumbing:
        ``slots`` divided by logical exchanges recovers the average
        capacity a solve actually used, which is how the
        shrinking-capacity schedule is audited without re-deriving
        capacities from the code.  Unit: slots (rows), not bytes.
        Conservation law (asserted in tests/test_comm.py): one
        request/reply lookup contributes exactly ``2 * p * capacity`` —
        never more; the primitives below only ever *carry* these fields
        through (``_replace``), so a caller cannot double-book a call by
        threading the same accumulator into both legs.
      * ``injected`` — float32 count of items affected by an active
        fault-injection plan (``comm/faults.py``, ISSUE 7), psum'd like
        ``items``: suppressed (stall), corrupted, misrouted, clipped or
        dropped items each count once at the exchange that faulted
        them, so a chaos run can assert every injected fault is
        attributable.  Always 0 outside ``faults.inject`` — the fault
        hooks trace no code when no plan is active.
      * ``hits`` / ``misses`` / ``pushed`` — float32 ghost-label-cache
        counters (ISSUE 4), psum'd like ``items``.  ``misses`` counts
        routed endpoint-lookup request items (with the cache disabled
        every endpoint lookup is by definition a miss, so this is also
        the per-round routed-lookup-volume counter the benchmarks
        track); ``hits`` counts endpoint reads served from the local
        ghost table (one per coalesced run that would otherwise have
        sent a request); ``pushed`` counts the cache's *entire*
        maintenance traffic — the root-delta items multicast through
        ``scatter_updates`` plus the subscription build/forward
        exchange items that keep the subscriber bitmasks with the
        surviving roots — so ``misses + pushed`` covers everything the
        cache ships.  The exchange primitives never touch these
        fields — only the sharded engine's lookup/push sites do.

    ``CommStats`` (core/distributed.py) is the engine-level view of the
    same counters (calls/items/bytes plus the Borůvka round count and
    the ghost hit/miss/push triple); the replicated engine derives those
    analytically, the sharded engine sums these accumulators, so
    benchmarks compare engines like-for-like.
    """
    calls: jax.Array   # [] int32   — all_to_all invocations
    items: jax.Array   # [] float32 — routed payload items (psum'd)
    bytes: jax.Array   # [] float32 — capacity-padded buffer bytes
    slots: jax.Array   # [] float32 — p * capacity rows per logical exchange
    hits: jax.Array    # [] float32 — ghost-cache label reads served locally
    misses: jax.Array  # [] float32 — routed endpoint-lookup request items
    pushed: jax.Array  # [] float32 — dirty labels multicast to subscribers
    injected: jax.Array  # [] float32 — fault-injected items (ISSUE 7)

    @staticmethod
    def zeros() -> "ExchangeStats":
        return ExchangeStats(jnp.int32(0), jnp.float32(0.0),
                             jnp.float32(0.0), jnp.float32(0.0),
                             jnp.float32(0.0), jnp.float32(0.0),
                             jnp.float32(0.0), jnp.float32(0.0))


def _hops(axis_names: Sequence[str], schedule: str) -> int:
    """all_to_all invocations one logical exchange costs (grid: one/axis)."""
    names = tuple(axis_names)
    return 1 if (schedule == "direct" or len(names) == 1) else len(names)


def _buffer_bytes(buffers) -> int:
    """Bytes one exchange of the (already [p, C, ...]-shaped) buffers ships."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(buffers))


class ExchangeResult(NamedTuple):
    """One routed exchange's receive-side view plus the bookkeeping a
    later ``reply`` needs to route answers back.  ``capacity`` (``C``
    below) is a per-call argument: two exchanges in the same program may
    use different capacities — the sharded engine's shrinking schedule
    relies on exactly that — and each call's capacity is recorded in
    ``stats.slots``."""
    recv: jax.Array        # [p, C, ...] received payloads (source-major)
    recv_ok: jax.Array     # [p, C] bool — slot holds a delivered item
    sent_ok: jax.Array     # [L] bool — item was within capacity
    dest: jax.Array        # [L] int32 (echoed)
    slot: jax.Array        # [L] int32 position used in the send buffer
    overflow: jax.Array    # [] int32 dropped-item count, psum'd (0 =>
    #                        results exact; > 0 => caller must retry
    #                        with a larger capacity — never silent)
    stats: Optional[ExchangeStats] = None  # set iff the caller threads one


def _group_positions(dest: jax.Array, valid: jax.Array, p: int) -> jax.Array:
    """Rank of each item within its destination group (stable)."""
    L = dest.shape[0]
    key = jnp.where(valid, dest, p)  # invalid items sort to the end
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    idx = jnp.arange(L, dtype=jnp.int32)
    first = jnp.searchsorted(sorted_key, sorted_key, side="left"
                             ).astype(jnp.int32)
    pos_sorted = idx - first
    return jnp.zeros((L,), jnp.int32).at[order].set(pos_sorted)


@obs.scope("exchange")
def routed_exchange(payload, dest: jax.Array, valid: jax.Array,
                    capacity: int, axis_names: Sequence[str],
                    schedule: str = "grid",
                    stats: Optional[ExchangeStats] = None,
                    site: str = "") -> ExchangeResult:
    """Deliver ``payload[i]`` to shard ``dest[i]``; static [p, C] buffers.

    ``payload`` is a pytree of [L, ...] arrays.  Must run inside shard_map
    with all ``axis_names`` present.  When ``stats`` is given, the result's
    ``stats`` field carries it plus this exchange's contribution.

    ``site`` labels this call for fault injection (``comm/faults.py``,
    ISSUE 7): while a ``FaultPlan`` is active, specs matching the label
    are applied at trace time and the affected-item count rides
    ``stats.injected``.  With no active plan (the default, and always
    outside ``faults.inject``) the fault hooks trace nothing — the
    fault-free program is bit-identical to one built before this
    parameter existed.
    """
    names = tuple(axis_names)
    p = 1
    for n in names:
        p *= compat.axis_size(n)
    L = dest.shape[0]
    cap_ok = capacity
    fspecs = faults.specs_for(site)
    inj = None
    if fspecs:
        payload, dest, valid, cap_ok, inj = faults.apply_send(
            fspecs, faults.active().seed, site, payload, dest, valid,
            capacity, p, names)
    pos = _group_positions(dest, valid, p)
    ok = valid & (pos < cap_ok) & (dest >= 0) & (dest < p)
    if fspecs and cap_ok < capacity:
        # clip: the admission rows a genuine capacity would have taken
        # are forced overflow — charge them to the injected counter too
        inj = inj + jnp.sum((valid & (pos >= cap_ok)
                             & (pos < capacity)).astype(jnp.float32))
    # predicated scatter: out-of-range rows are dropped
    d_idx = jnp.where(ok, dest, p)
    s_idx = jnp.where(ok, pos, 0)

    def scatter(x):
        # freshly created buffers are unvarying; promote them before the
        # scatter of per-shard data so the module passes check_vma on
        # JAX >= 0.6 (no-op on 0.4.x — see repro.compat)
        buf = compat.vary(jnp.zeros((p, capacity) + x.shape[1:], x.dtype),
                          names)
        return buf.at[d_idx, s_idx].set(x, mode="drop")

    send = jax.tree.map(scatter, payload)
    send_mask = compat.vary(jnp.zeros((p, capacity), bool), names).at[
        d_idx, s_idx].set(ok, mode="drop")
    recv = jax.tree.map(lambda b: all_to_all_nd(b, names, schedule), send)
    recv_ok = all_to_all_nd(send_mask, names, schedule)
    if fspecs:
        recv_ok, inj_r = faults.apply_recv(fspecs, faults.active().seed,
                                           site, recv_ok, names)
        inj = inj + inj_r
    overflow = lax.psum(jnp.sum((valid & ~ok).astype(jnp.int32)), names)
    if stats is not None:
        h = _hops(names, schedule)
        nbuf = len(jax.tree.leaves(payload)) + 1  # + validity mask
        by = _buffer_bytes(send) + _buffer_bytes(send_mask)
        items = lax.psum(jnp.sum(ok.astype(jnp.float32)), names)
        stats = stats._replace(calls=stats.calls + jnp.int32(nbuf * h),
                               items=stats.items + items,
                               bytes=stats.bytes + jnp.float32(by * h),
                               slots=stats.slots + jnp.float32(p * capacity))
        if fspecs:
            stats = stats._replace(
                injected=stats.injected + lax.psum(inj, names))
    return ExchangeResult(recv, recv_ok, ok, dest, pos, overflow, stats)


@obs.scope("exchange")
def reply(ex: ExchangeResult, answers, axis_names: Sequence[str],
          schedule: str = "grid", stats: Optional[ExchangeStats] = None):
    """Route per-slot ``answers`` ([p, C, ...], aligned with ``ex.recv``)
    back to the requesting items.  Returns [L, ...] with ``ex.sent_ok``
    telling which entries are meaningful; with ``stats``, returns
    ([L, ...], updated stats) instead."""
    names = tuple(axis_names)
    back = jax.tree.map(lambda a: all_to_all_nd(a, names, schedule), answers)
    # item i used buffer position (dest[i], slot[i]); after the return
    # exchange, that slot holds the answer from shard dest[i].
    d = jnp.clip(ex.dest, 0, None)

    def gather(b):
        return b[d, ex.slot]

    out = jax.tree.map(gather, back)
    if stats is None:
        return out
    h = _hops(names, schedule)
    by = _buffer_bytes(answers)
    items = lax.psum(jnp.sum(ex.recv_ok.astype(jnp.float32)), names)
    leaves = jax.tree.leaves(answers)
    nbuf = len(leaves)
    slots = leaves[0].shape[0] * leaves[0].shape[1] if leaves else 0
    stats = stats._replace(calls=stats.calls + jnp.int32(nbuf * h),
                           items=stats.items + items,
                           bytes=stats.bytes + jnp.float32(by * h),
                           slots=stats.slots + jnp.float32(slots))
    return out, stats


def _mask_to_copies(dest_mask: jax.Array, valid: jax.Array,
                    p: int) -> jax.Array:
    """Expand per-item int32 destination bitmasks to the [L, p] copy
    matrix ``scatter_updates`` routes from: copy (i, s) exists iff item
    ``i`` is valid and bit ``s`` of ``dest_mask[i]`` is set.

    Pure bit arithmetic, factored out so the width contract is testable
    without a mesh (tests/test_comm.py): bits 0..30 are usable
    destinations, bit 31 is the int32 sign bit — which is why callers
    (the ghost cache) must fall back beyond 31 shards, and why this
    helper is only ever called with ``p <= 31``.
    """
    lanes = jnp.arange(p, dtype=jnp.int32)
    return valid[:, None] & (((dest_mask[:, None] >> lanes) & 1) > 0)


def _axis_masks_to_copies(row_mask: jax.Array, col_mask: jax.Array,
                          valid: jax.Array, r: int, c: int
                          ) -> Tuple[jax.Array, jax.Array]:
    """Per-axis sibling of ``_mask_to_copies`` for the two-level grid
    multicast: expand a *pair* of per-axis int32 subscription bitmasks
    into the two per-hop copy matrices.

    Returns ``(row_copies [L, r], col_copies [L, c])``: copy (i, rr)
    exists iff item ``i`` is valid and bit ``rr`` of ``row_mask[i]`` is
    set (the deputy's second hop down its column), copy (i, cc) likewise
    from ``col_mask`` (the owner's first hop along its row).  The
    delivered set is the outer product ``row_copies & col_copies`` —
    every device (rr, cc) with both bits set — which covers up to
    31 x 31 = 961 shards from two sign-bit-safe int32 masks, while each
    hop's transient stays [L, <=31] instead of the flat [L, p].
    Pure bit arithmetic (meshless-testable, tests/test_comm.py); both
    axes share the flat helper's bit-30 width contract.
    """
    return (_mask_to_copies(row_mask, valid, r),
            _mask_to_copies(col_mask, valid, c))


class ScatterResult(NamedTuple):
    """Receive-side view of one ``scatter_updates`` multicast.  There is
    no reply leg, so no routing bookkeeping is carried — consumers apply
    the received updates in place (e.g. scatter new labels into a ghost
    table) and only need the source-major buffers plus the overflow
    contract shared with ``routed_exchange``."""
    recv: jax.Array      # [p, C, ...] received payloads (source-major)
    recv_ok: jax.Array   # [p, C] bool — slot holds a delivered item
    sent_ok: jax.Array   # [L, p] bool — (item, dest) copy was in capacity
    overflow: jax.Array  # [] int32 dropped (item, dest) copies, psum'd
    stats: Optional[ExchangeStats] = None


def scatter_updates(payload, dest_mask: jax.Array, valid: jax.Array,
                    capacity: int, axis_names: Sequence[str],
                    schedule: str = "grid",
                    stats: Optional[ExchangeStats] = None,
                    site: str = "") -> ScatterResult:
    """Multicast ``payload[i]`` to every shard set in bitmask ``dest_mask[i]``.

    The push-style dual of ``routed_exchange``: no request leg, no reply
    routing — item ``i`` is copied into the send row of every
    destination shard ``s`` with ``dest_mask[i] >> s & 1`` set (so one
    changed ghost label reaches all its subscribers in a single
    exchange).  ``dest_mask`` is an int32 bitmask, which caps the mesh
    at 31 shards for this primitive (bit 31 would be the int32 sign
    bit); callers gate on that and fall back to per-destination
    request/reply beyond it.  Per-destination positions come from one
    column-wise cumsum over the [L, p] copy mask — an O(L·p) transient,
    the price of static shapes for a multicast (documented honestly in
    docs/ARCHITECTURE.md).

    Overflow accounting matches ``routed_exchange``: copies beyond
    ``capacity`` are dropped *per destination* and counted, never
    silent.  ``stats`` accrues one logical exchange (payload leaves + 1
    mask buffer) with the grid hop multiplier on slots as well as bytes
    — a multicast's copies are *re-admitted* at every hop, so a
    d-axis grid schedule allocates ``p * capacity * d`` rows, unlike
    the request/reply legs whose pre-packed buffer ships end to end
    (see the ``ExchangeStats.slots`` contract); the ghost-specific
    ``pushed`` counter is the caller's to bump — this primitive is
    generic.
    """
    names = tuple(axis_names)
    p = 1
    for n in names:
        p *= compat.axis_size(n)
    L = dest_mask.shape[0]
    cap_ok = capacity
    fspecs = faults.specs_for(site)
    inj = None
    if fspecs:
        payload, dest_mask, valid, cap_ok, inj = faults.apply_send_scatter(
            fspecs, faults.active().seed, site, payload, dest_mask,
            valid, capacity, p, names)
    want = _mask_to_copies(dest_mask, valid, p)
    pos = jnp.cumsum(want.astype(jnp.int32), axis=0) - 1     # [L, p]
    ok = want & (pos < cap_ok)
    if fspecs and cap_ok < capacity:
        inj = inj + jnp.sum((want & (pos >= cap_ok)
                             & (pos < capacity)).astype(jnp.float32))
    d_idx = jnp.where(ok, jnp.arange(p, dtype=jnp.int32)[None, :], p)
    s_idx = jnp.where(ok, pos, 0)

    def scatter(x):
        buf = compat.vary(jnp.zeros((p, capacity) + x.shape[1:], x.dtype),
                          names)
        rep = jnp.broadcast_to(x[:, None], (L, p) + x.shape[1:])
        return buf.at[d_idx, s_idx].set(rep, mode="drop")

    send = jax.tree.map(scatter, payload)
    send_mask = compat.vary(jnp.zeros((p, capacity), bool), names).at[
        d_idx, s_idx].set(ok, mode="drop")
    recv = jax.tree.map(lambda b: all_to_all_nd(b, names, schedule), send)
    recv_ok = all_to_all_nd(send_mask, names, schedule)
    if fspecs:
        recv_ok, inj_r = faults.apply_recv(fspecs, faults.active().seed,
                                           site, recv_ok, names)
        inj = inj + inj_r
    overflow = lax.psum(jnp.sum((want & ~ok).astype(jnp.int32)), names)
    if stats is not None:
        h = _hops(names, schedule)
        nbuf = len(jax.tree.leaves(payload)) + 1  # + validity mask
        by = _buffer_bytes(send) + _buffer_bytes(send_mask)
        items = lax.psum(jnp.sum(ok.astype(jnp.float32)), names)
        stats = stats._replace(calls=stats.calls + jnp.int32(nbuf * h),
                               items=stats.items + items,
                               bytes=stats.bytes + jnp.float32(by * h),
                               slots=stats.slots
                               + jnp.float32(p * capacity * h))
        if fspecs:
            stats = stats._replace(
                injected=stats.injected + lax.psum(inj, names))
    return ScatterResult(recv, recv_ok, ok, overflow, stats)


def scatter_updates_grid(payload, row_mask: jax.Array,
                         col_mask: jax.Array, valid: jax.Array,
                         cap_row: int, cap_col: int,
                         axis_names: Sequence[str],
                         stats: Optional[ExchangeStats] = None,
                         site_row: str = "", site_col: str = ""
                         ) -> ScatterResult:
    """Two-level grid multicast (Section VI-A applied to the push).

    Delivers ``payload[i]`` to every device ``(rr, cc)`` with bit ``rr``
    of ``row_mask[i]`` *and* bit ``cc`` of ``col_mask[i]`` set, in two
    hops on a 2-axis ``(row, col)`` mesh:

      1. the owner at ``(r0, c0)`` ships one copy per subscribing
         column along its own row — an ``all_to_all`` over the *col*
         axis only, ``[C, cap_row]`` buffers — to the grid deputies
         ``(r0, cc)``, each copy carrying its ``row_mask``;
      2. each deputy re-multicasts its received items down its column
         to the subscribing rows — an ``all_to_all`` over the *row*
         axis, ``[R, cap_col]`` buffers.

    Per hop the copy matrix is ``[*, <=31]`` instead of the flat
    ``[L, p]``, the per-item fan-out is O(sqrt(p)) instead of O(p), and
    the pair of int32 masks addresses up to 961 shards — the flat
    primitive's 31-shard sign-bit cap, lifted.  The delivered set is
    the *outer product* of the two masks, a superset of any true
    subscriber set whose projections they are; callers must apply
    updates value-keyed (the ghost push rewrites table entries matching
    the shipped old root, so an unsubscribed ``(rr, cc)`` in the cross
    product simply matches nothing).

    Overflow follows the shared exchange contract on **both** hops:
    copies beyond ``cap_row`` per (owner, column) or beyond ``cap_col``
    per (deputy, row) are dropped and counted, never silent.  ``stats``
    books the two legs distinctly — hop 1 adds ``C * cap_row`` slots
    (payload leaves + the forwarded row mask + validity), hop 2
    ``R * cap_col`` — so the roofline cross-check sees the deputy leg's
    real cost.  ``site_row`` / ``site_col`` label the hops separately
    for fault injection (``ghost_push_row`` / ``ghost_push_col`` in the
    engine).  The result's ``sent_ok`` is the hop-1 admission matrix
    ``[L, C]`` (the owner's view; hop-2 drops are visible in
    ``overflow`` only, like any relayed exchange).
    """
    names = tuple(axis_names)
    if len(names) != 2:
        raise ValueError(
            f"scatter_updates_grid needs a (row, col) axis pair, got "
            f"{names!r}")
    row_ax, col_ax = names
    R = compat.axis_size(row_ax)
    C = compat.axis_size(col_ax)
    L = valid.shape[0]
    leaves = jax.tree.leaves(payload)

    # -- hop 1: owner -> deputies along the row (exchange over col) ------
    cap1_ok = cap_row
    fspecs1 = faults.specs_for(site_row)
    inj = jnp.float32(0.0)
    pl1 = (payload, row_mask)
    if fspecs1:
        pl1, col_mask, valid, cap1_ok, inj = faults.apply_send_scatter(
            fspecs1, faults.active().seed, site_row, pl1, col_mask,
            valid, cap_row, C, names)
    want1 = _mask_to_copies(col_mask, valid, C)          # [L, C]
    pos1 = jnp.cumsum(want1.astype(jnp.int32), axis=0) - 1
    ok1 = want1 & (pos1 < cap1_ok)
    if fspecs1 and cap1_ok < cap_row:
        inj = inj + jnp.sum((want1 & (pos1 >= cap1_ok)
                             & (pos1 < cap_row)).astype(jnp.float32))
    d1 = jnp.where(ok1, jnp.arange(C, dtype=jnp.int32)[None, :], C)
    s1 = jnp.where(ok1, pos1, 0)

    def scatter1(x):
        buf = compat.vary(jnp.zeros((C, cap_row) + x.shape[1:], x.dtype),
                          names)
        rep = jnp.broadcast_to(x[:, None], (L, C) + x.shape[1:])
        return buf.at[d1, s1].set(rep, mode="drop")

    send1 = jax.tree.map(scatter1, pl1)
    mask1 = compat.vary(jnp.zeros((C, cap_row), bool), names).at[
        d1, s1].set(ok1, mode="drop")
    hop1 = jax.tree.map(
        lambda b: lax.all_to_all(b, col_ax, split_axis=0, concat_axis=0),
        send1)
    ok_r = lax.all_to_all(mask1, col_ax, split_axis=0, concat_axis=0)
    if fspecs1:
        ok_r, inj_r = faults.apply_recv(fspecs1, faults.active().seed,
                                        site_row, ok_r, names)
        inj = inj + inj_r
    ovf1 = lax.psum(jnp.sum((want1 & ~ok1).astype(jnp.int32)), names)
    recv_payload, rmask_r = hop1                         # [C, cap_row, ...]

    # -- hop 2: deputy -> subscribers down the column (exchange over row)
    M = C * cap_row
    dep_valid = ok_r.reshape(-1)
    dep_rmask = rmask_r.reshape(-1)
    dep_payload = jax.tree.map(
        lambda x: x.reshape((M,) + x.shape[2:]), recv_payload)
    cap2_ok = cap_col
    fspecs2 = faults.specs_for(site_col)
    if fspecs2:
        (dep_payload, dep_rmask, dep_valid, cap2_ok,
         inj2) = faults.apply_send_scatter(
            fspecs2, faults.active().seed, site_col, dep_payload,
            dep_rmask, dep_valid, cap_col, R, names)
        inj = inj + inj2
    want2 = _mask_to_copies(dep_rmask, dep_valid, R)     # [M, R]
    pos2 = jnp.cumsum(want2.astype(jnp.int32), axis=0) - 1
    ok2 = want2 & (pos2 < cap2_ok)
    if fspecs2 and cap2_ok < cap_col:
        inj = inj + jnp.sum((want2 & (pos2 >= cap2_ok)
                             & (pos2 < cap_col)).astype(jnp.float32))
    d2 = jnp.where(ok2, jnp.arange(R, dtype=jnp.int32)[None, :], R)
    s2 = jnp.where(ok2, pos2, 0)

    def scatter2(x):
        buf = compat.vary(jnp.zeros((R, cap_col) + x.shape[1:], x.dtype),
                          names)
        rep = jnp.broadcast_to(x[:, None], (M, R) + x.shape[1:])
        return buf.at[d2, s2].set(rep, mode="drop")

    send2 = jax.tree.map(scatter2, dep_payload)
    mask2 = compat.vary(jnp.zeros((R, cap_col), bool), names).at[
        d2, s2].set(ok2, mode="drop")
    recv = jax.tree.map(
        lambda b: lax.all_to_all(b, row_ax, split_axis=0, concat_axis=0),
        send2)
    recv_ok = lax.all_to_all(mask2, row_ax, split_axis=0, concat_axis=0)
    if fspecs2:
        recv_ok, inj_r2 = faults.apply_recv(fspecs2, faults.active().seed,
                                            site_col, recv_ok, names)
        inj = inj + inj_r2
    ovf2 = lax.psum(jnp.sum((want2 & ~ok2).astype(jnp.int32)), names)

    if stats is not None:
        nbuf1 = len(leaves) + 2          # + row mask + validity mask
        nbuf2 = len(leaves) + 1          # + validity mask
        by = (_buffer_bytes(send1) + _buffer_bytes(mask1)
              + _buffer_bytes(send2) + _buffer_bytes(mask2))
        items = lax.psum(jnp.sum(ok1.astype(jnp.float32))
                         + jnp.sum(ok2.astype(jnp.float32)), names)
        stats = stats._replace(
            calls=stats.calls + jnp.int32(nbuf1 + nbuf2),
            items=stats.items + items,
            bytes=stats.bytes + jnp.float32(by),
            slots=stats.slots + jnp.float32(C * cap_row + R * cap_col))
        if fspecs1 or fspecs2:
            stats = stats._replace(
                injected=stats.injected + lax.psum(inj, names))
    return ScatterResult(recv, recv_ok, ok1, ovf1 + ovf2, stats)


def request_reply(request, dest: jax.Array, valid: jax.Array,
                  answer_fn: Callable, capacity: int,
                  axis_names: Sequence[str], schedule: str = "grid",
                  site: str = ""
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """EXCHANGELABELS pattern: ship requests home, answer, ship answers back.

    ``answer_fn(recv, recv_ok) -> answers`` runs on the home shard with
    [p, C, ...] inputs.  Returns (answers[L, ...], answered[L] bool,
    overflow count)."""
    ex = routed_exchange(request, dest, valid, capacity, axis_names, schedule,
                         site=site)
    answers = answer_fn(ex.recv, ex.recv_ok)
    out = reply(ex, answers, axis_names, schedule)
    return out, ex.sent_ok, ex.overflow
