"""Pallas TPU kernel: block-segmented min-edge reduction (MINEDGES).

The paper's hottest per-round primitive is the per-component minimum
incident edge (Fig. 6 phase "min edge computation"; the shared-memory
variant uses parlay Min-Priority-Write).  A GPU port would use atomics;
TPUs have none — the TPU-native decomposition is:

  phase 1 (this kernel): block-local *segmented prefix-min scan* over the
    lexicographically sorted edge array held in VMEM, emitting per-edge
    boundary candidates — (min w, argmin eid) at the last edge of every
    equal-`seg` run, neutral elements elsewhere.  The scan is
    Hillis-Steele with a run guard: log2(block) unrolled vector steps,
    pure VPU ops, no gather/scatter, no atomics.  Because the edge array
    is sorted by source vertex, each source's run is contiguous, so the
    candidate count per block is the number of distinct sources, not the
    number of edges.

  phase 2 (ops.py, plain jnp): scatter-min of the candidates into the
    dense per-vertex vectors — the same dense vectors the replicated
    base case allReduces (Section IV-D), so the kernel output feeds the
    distributed pipeline directly.

Run semantics: runs are *contiguous* stretches of equal ``seg``; the seg
array need not be globally sorted (after contraction, ``seg = labels[u]``
is only piecewise constant in u), which phase 2 handles by combining
candidates of runs that share a component.

  phase 3 (``owner_scatter_min``, ISSUE 8): the fused min-semiring
    scatter the sharded engine's MINEDGES runs on both sides of the
    routed exchange — the pre-routing per-run (w, eid)-argmin combine
    and the owner-side per-component scatter-min — as one Pallas kernel
    over arbitrary (unsorted) slot indices, replacing the five-scatter
    jnp sequence without materialising its intermediate tables.

The (w, eid) pair is reduced lexicographically — the direction-independent
total order that keeps Borůvka cycle-free under ties.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro import compat

EID_SENTINEL = 2 ** 30


def default_interpret() -> bool:
    """Backend-aware Pallas mode: compile on the TPU the kernels target,
    interpret on the CPU (tests).  Any other backend raises: the kernels
    are written for Mosaic, and interpreting them there would hide the
    device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"the Pallas kernels target the TPU (compiled) and the CPU "
        f"(interpreted); backend {backend!r} is neither")


def _segmin_kernel(seg_ref, w_ref, eid_ref, alive_ref, cw_ref, ce_ref,
                   *, block: int):
    seg = seg_ref[...]
    w = w_ref[...].astype(jnp.float32)
    eid = eid_ref[...]
    alive = alive_ref[...] != 0

    inf = jnp.float32(jnp.inf)
    sent = jnp.int32(EID_SENTINEL)
    val_w = jnp.where(alive, w, inf)
    val_e = jnp.where(alive, eid, sent)

    # Hillis-Steele segmented prefix-min: after step d the value at i
    # covers the last 2d elements of its run; min is idempotent, so
    # over-inclusive windows within one run are harmless.
    d = 1
    while d < block:
        pad_w = jnp.full((d,), inf, jnp.float32)
        pad_e = jnp.full((d,), sent, jnp.int32)
        pad_s = jnp.full((d,), -1, seg.dtype)
        sh_w = jnp.concatenate([pad_w, val_w[:-d]])
        sh_e = jnp.concatenate([pad_e, val_e[:-d]])
        sh_s = jnp.concatenate([pad_s, seg[:-d]])
        same = sh_s == seg
        better = same & (sh_w < val_w)
        tie = same & (sh_w == val_w)
        val_e = jnp.where(better, sh_e,
                          jnp.where(tie, jnp.minimum(val_e, sh_e), val_e))
        val_w = jnp.where(better, sh_w, val_w)
        d *= 2

    # boundary = last edge of its run inside this block
    nxt = jnp.concatenate([seg[1:], jnp.full((1,), -1, seg.dtype)])
    is_last = seg != nxt  # the final element always differs from -1
    cw_ref[...] = jnp.where(is_last, val_w, inf)
    ce_ref[...] = jnp.where(is_last, val_e, sent)


def _scatter_min_kernel(idx_ref, w_ref, eid_ref, p1_ref, p2_ref,
                        wt_ref, et_ref, p1t_ref, p2t_ref, *,
                        out_block: int, block: int):
    """Fused min-semiring scatter: one grid step folds one candidate
    block into one output tile's (w, eid, payload) accumulator.

    Grid is (out tiles, candidate blocks) with the candidate dimension
    innermost, so the output tile persists in VMEM across the whole
    candidate sweep (initialised at the first step).  Candidates arrive
    as ``(1, block)`` lane rows and the tile is an ``(out_block, 1)``
    column, so the [out_block, block] one-hot hit matrix — the
    TPU-native replacement for the scatter the jnp path pays five times
    — is a broadcast compare, reduced along lanes to the tile's
    block-local (min w, min eid among w-ties, payload at the (w, eid)
    winner); a lexicographic combine then folds the block triple into
    the accumulator.  Payload-at-winner is reduced with max, which is
    exact because candidates tied on the full (w, eid) key carry
    identical payloads (both directed copies of an undirected edge ship
    the same eid and the same opposing component) — the same argument
    the jnp path's ``.at[].max`` relies on.  Lanes with ``idx < 0`` are
    gated off (they never equal a tile row).

    A sparse-band guard skips candidate blocks whose live index range
    cannot touch this tile: for the pre-routing per-run combine the
    index row (``run_id``) is non-decreasing, so each candidate block
    intersects O(1) tiles and the compute degenerates to the band.
    Owner-side (unsorted ``comp - base``) it simply never fires.  The
    grid itself stays (tiles x blocks) either way.
    """
    c = pl.program_id(1)

    inf = jnp.float32(jnp.inf)
    sent = jnp.int32(EID_SENTINEL)

    @pl.when(c == 0)
    def _init():
        wt_ref[...] = jnp.full((out_block, 1), inf, jnp.float32)
        et_ref[...] = jnp.full((out_block, 1), sent, jnp.int32)
        p1t_ref[...] = jnp.full((out_block, 1), -1, jnp.int32)
        p2t_ref[...] = jnp.full((out_block, 1), -1, jnp.int32)

    idx = idx_ref[...]
    row0 = pl.program_id(0) * out_block
    lo = jnp.min(jnp.where(idx >= 0, idx, jnp.int32(2 ** 31 - 1)))
    hi = jnp.max(idx)

    @pl.when((lo < row0 + out_block) & (hi >= row0))
    def _accumulate():
        w = w_ref[...].astype(jnp.float32)
        eid = eid_ref[...]
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32,
                                               (out_block, block), 0)
        hit = idx == rows
        wv = jnp.where(hit, w, inf)
        wb = jnp.min(wv, axis=1, keepdims=True)
        tie = hit & (wv == wb)
        eb = jnp.min(jnp.where(tie, eid, sent), axis=1, keepdims=True)
        winm = tie & (eid == eb)
        p1b = jnp.max(jnp.where(winm, p1_ref[...], -1), axis=1,
                      keepdims=True)
        p2b = jnp.max(jnp.where(winm, p2_ref[...], -1), axis=1,
                      keepdims=True)

        cw, ce = wt_ref[...], et_ref[...]
        better = wb < cw
        wtie = wb == cw
        e_better = wtie & (eb < ce)
        e_tie = wtie & (eb == ce)
        take = better | e_better
        wt_ref[...] = jnp.minimum(cw, wb)
        et_ref[...] = jnp.where(better, eb,
                                jnp.where(wtie, jnp.minimum(ce, eb), ce))
        p1t_ref[...] = jnp.where(take, p1b,
                                 jnp.where(e_tie,
                                           jnp.maximum(p1t_ref[...], p1b),
                                           p1t_ref[...]))
        p2t_ref[...] = jnp.where(take, p2b,
                                 jnp.where(e_tie,
                                           jnp.maximum(p2t_ref[...], p2b),
                                           p2t_ref[...]))


@functools.partial(jax.jit, static_argnames=("size", "block", "out_block",
                                             "interpret"))
def owner_scatter_min(idx: jax.Array, w: jax.Array, eid: jax.Array,
                      pay1: jax.Array, pay2: jax.Array, ok: jax.Array,
                      size: int, *, block: int = 512,
                      out_block: int = 256,
                      interpret: Optional[bool] = None):
    """Fused (w, eid)-lexicographic scatter-min into ``size`` slots.

    The phase-3 MINEDGES kernel (ISSUE 8): candidates ``(idx, w, eid,
    pay1, pay2)`` gated by ``ok`` reduce into per-slot tables — exactly
    the reduction both MINEDGES sites of the sharded engine perform:

      * owner side, ``idx = comp - base``: the routed candidates'
        per-owned-component winner tables;
      * pre-routing combine, ``idx = run_id``: the per-source-run
        (w, eid)-argmin tables (run ids are one more ownership index,
        so one kernel serves both sites — the min-semiring framing of
        PAPERS.md arxiv 2110.04865 made concrete).

    Returns ``(wmin f32 [size], emin i32 [size], pay1 i32 [size],
    pay2 i32 [size])`` with defaults ``(inf, EID_SENTINEL, -1, -1)``;
    ``pay*`` carry the payloads of the (w, eid) winner.  Bit-identical
    to the jnp ``.at[].min``/``.at[].max`` path for any candidate order
    (min/max are associative-commutative and payloads are constant
    across exact (w, eid) ties).  ``ok=False`` lanes never contribute —
    their ``idx`` may be garbage.  The grid has
    ``ceil(size / out_block) * ceil(L / block)`` steps, so the cost
    grows with ``size * L``.  Same block/``interpret`` discipline as
    ``segmin_candidates``.
    """
    if interpret is None:
        interpret = default_interpret()
    # the manual axes the inputs vary over (none outside shard_map): the
    # kernel's outputs must declare them
    vma = frozenset().union(*map(compat.vma_of,
                                 (idx, w, eid, pay1, pay2, ok)))
    impl = functools.partial(_owner_scatter_min, size=size, block=block,
                             out_block=out_block, interpret=interpret,
                             vma=vma)
    if interpret and vma:
        # The HLO interpreter evaluates the kernel body with shard_map's
        # varying-axes checks on, though the body was traced with them
        # off, and rejects it.  Interpret it inside a shard_map over no
        # further axes with the checks off: the values stay per shard.
        impl = jax.shard_map(impl, in_specs=P(), out_specs=P(),
                             axis_names=frozenset(), check_vma=False)
    return tuple(compat.vary(x, tuple(vma))
                 for x in impl(idx, w, eid, pay1, pay2, ok))


def _owner_scatter_min(idx, w, eid, pay1, pay2, ok, *, size: int,
                       block: int, out_block: int, interpret,
                       vma: frozenset):
    L = idx.shape[0]
    if L == 0 or size == 0:
        return (jnp.full((size,), jnp.inf, jnp.float32),
                jnp.full((size,), EID_SENTINEL, jnp.int32),
                jnp.full((size,), -1, jnp.int32),
                jnp.full((size,), -1, jnp.int32))
    # lane blocks are multiples of 128 and sublane tiles multiples of 8,
    # or the whole (padded) axis: the tiling Mosaic accepts
    block = min(block, L + (-L) % 128)
    out_block = min(out_block, size + (-size) % 8)
    idx = jnp.where(ok, idx, -1)  # gate folded into the index row
    pad = (-L) % block
    if pad:
        idx = jnp.concatenate([idx, jnp.full((pad,), -1, idx.dtype)])
        w = jnp.concatenate([w, jnp.full((pad,), jnp.inf, w.dtype)])
        eid = jnp.concatenate([eid, jnp.full((pad,), EID_SENTINEL,
                                             eid.dtype)])
        pay1 = jnp.concatenate([pay1, jnp.full((pad,), -1, pay1.dtype)])
        pay2 = jnp.concatenate([pay2, jnp.full((pad,), -1, pay2.dtype)])
    sp = size + ((-size) % out_block)
    grid = (sp // out_block, idx.shape[0] // block)
    cspec = pl.BlockSpec((1, block), lambda o, c: (0, c))
    ospec = pl.BlockSpec((out_block, 1), lambda o, c: (o, 0))
    out_shape = [jax.ShapeDtypeStruct((sp, 1), dt, vma=vma)
                 for dt in (jnp.float32, jnp.int32, jnp.int32, jnp.int32)]
    outs = pl.pallas_call(
        functools.partial(_scatter_min_kernel, out_block=out_block,
                          block=block),
        grid=grid,
        in_specs=[cspec] * 5,
        out_specs=[ospec] * 4,
        out_shape=out_shape,
        interpret=interpret,
    )(*(x[None, :] for x in (idx, w, eid, pay1, pay2)))
    return tuple(x[:size, 0] for x in outs)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def segmin_candidates(seg: jax.Array, w: jax.Array, eid: jax.Array,
                      alive: jax.Array, *, block: int = 512,
                      interpret: Optional[bool] = None):
    """Phase-1 kernel call. Arrays are padded to a multiple of ``block``.

    Padding entries must carry alive=False (any seg value).  Returns
    (cand_w f32 [M], cand_eid i32 [M]).  ``interpret=None`` resolves
    via ``default_interpret()`` (compiled on TPU, interpreted on the CPU).
    """
    if interpret is None:
        interpret = default_interpret()
    m = seg.shape[0]
    block = min(block, max(m, 8))
    pad = (-m) % block
    if pad:
        seg = jnp.concatenate([seg, jnp.full((pad,), -1, seg.dtype)])
        w = jnp.concatenate([w, jnp.full((pad,), jnp.inf, w.dtype)])
        eid = jnp.concatenate([eid, jnp.full((pad,), EID_SENTINEL,
                                             eid.dtype)])
        alive = jnp.concatenate([alive, jnp.zeros((pad,), alive.dtype)])
    mp = seg.shape[0]
    grid = (mp // block,)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    cand_w, cand_e = pl.pallas_call(
        functools.partial(_segmin_kernel, block=block),
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((mp,), jnp.float32),
                   jax.ShapeDtypeStruct((mp,), jnp.int32)],
        interpret=interpret,
    )(seg, w, eid, alive.astype(jnp.int8))
    return cand_w[:m], cand_e[:m]
