"""Jitted public wrappers around the segmented-scan machinery.

``min_edges_dense`` is the dense per-vertex min-edge entry point (the
segmin kernel's phase 2).  ``run_metadata`` exposes the same
contiguous-run discipline the kernel's Hillis-Steele scan exploits as a
standalone jnp primitive: the sharded-label engine uses it to coalesce
label-lookup requests (one routed request per distinct source vertex
instead of one per edge slot — EXPERIMENTS.md §Sharded-label engine).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.segmin.ref import (EID_SENTINEL, dense_min_from_candidates,
                                      owner_scatter_min_ref,
                                      segmin_candidates_ref)
from repro.kernels.segmin.segmin import (default_interpret,
                                         owner_scatter_min,
                                         segmin_candidates)


def run_metadata(values: jax.Array, perm: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Contiguous equal-value run structure of ``values`` ([L]).

    Returns (head [L] bool — first slot of its run, head_idx [L] int32 —
    index of each slot's run head, run_id [L] int32 — dense run number).
    ``cummax``/``cumsum`` are the log-depth Hillis-Steele scans the segmin
    kernel runs block-wise; here they run array-wide because the result
    feeds a routed exchange, not a VMEM-resident reduction.  Pure
    shape-of-``values`` metadata: compute it once per edge array and
    reuse across rounds.

    With ``perm`` (an [L] int32 permutation) the runs are computed over
    the **permuted view** ``values[perm]`` and the returned metadata is
    in permuted-slot order.  This is the v-sorted secondary index of the
    sharded MST engine (ISSUE 4): the edge array is lexicographically
    ``(u, v)``-sorted, so equal-``v`` runs are short in slot order — but
    over ``perm = argsort(v)`` every distinct ``v`` is one maximal run,
    and both endpoint columns coalesce to one routed request per
    distinct vertex.  Callers map per-slot results back through
    ``out.at[perm].set(permuted_result)``.
    """
    if perm is not None:
        values = values[perm]
    L = values.shape[0]
    if L == 0:
        # the concatenate below would fabricate a length-1 head for an
        # empty array; an empty shard has no runs (the fused combine
        # kernel calls this on possibly-empty per-shard slices)
        z = jnp.zeros((0,), jnp.int32)
        return jnp.zeros((0,), bool), z, z
    idx = jnp.arange(L, dtype=jnp.int32)
    head = jnp.concatenate([jnp.ones((1,), bool),
                            values[1:] != values[:-1]])
    head_idx = lax.cummax(jnp.where(head, idx, jnp.int32(0)))
    run_id = jnp.cumsum(head.astype(jnp.int32)) - 1
    return head, head_idx, run_id


@functools.partial(jax.jit,
                   static_argnames=("n", "block", "interpret", "use_pallas"))
def min_edges_dense(seg: jax.Array, w: jax.Array, eid: jax.Array,
                    alive: jax.Array, n: int, *, block: int = 512,
                    interpret: Optional[bool] = None, use_pallas: bool = True
                    ) -> Tuple[jax.Array, jax.Array]:
    """Per-vertex (min weight, argmin eid) over contiguous-run edges.

    Two-phase: Pallas block-segmented scan -> tiny scatter-min combine.
    ``use_pallas=False`` routes through the pure-jnp oracle (same
    contract), which is what the CPU test/bench path uses by default.
    ``interpret=None`` resolves backend-aware (compiled on TPU,
    interpreted on the CPU).
    """
    if use_pallas:
        cw, ce = segmin_candidates(seg, w, eid, alive, block=block,
                                   interpret=interpret)
    else:
        cw, ce = segmin_candidates_ref(seg, w, eid, alive)
    return dense_min_from_candidates(seg, cw, ce, n)


@functools.partial(jax.jit,
                   static_argnames=("size", "block", "out_block",
                                    "interpret", "use_pallas"))
def scatter_min_tables(idx: jax.Array, w: jax.Array, eid: jax.Array,
                       pay1: jax.Array, pay2: jax.Array, ok: jax.Array,
                       size: int, *, block: int = 512,
                       out_block: int = 256,
                       interpret: Optional[bool] = None,
                       use_pallas: bool = True
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array]:
    """Fused (w, eid)-lexicographic scatter-min, dispatchable.

    The public face of the phase-3 kernel (``segmin.owner_scatter_min``)
    with the same ``use_pallas``/``interpret`` dispatch discipline as
    ``min_edges_dense``; ``use_pallas=False`` routes through the exact
    sequential oracle (``ref.owner_scatter_min_ref``) — the comparator
    the property wall pins both against.
    """
    if use_pallas:
        return owner_scatter_min(idx, w, eid, pay1, pay2, ok, size,
                                 block=block, out_block=out_block,
                                 interpret=interpret)
    return owner_scatter_min_ref(idx, w, eid, pay1, pay2, ok, size)
