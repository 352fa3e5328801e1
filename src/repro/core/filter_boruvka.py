"""Filter-Borůvka (Section V of the paper), two engines.

Static engine (jittable, what a TPU executes / what the dry-run lowers):
    Sort edges once by (w, idx).  Quantile pivots make the recursion a
    *static* schedule of equal-size ascending weight buckets; processing
    bucket b with the component labels accumulated from buckets < b is
    exactly Filter-Kruskal's light-then-filtered-heavy order (a batch
    contraction Kruskal), with a Borůvka run as the per-bucket base case.
    Filtering is the relabel gather: an edge inside an already-built
    component becomes a self-loop and is dead for the min-reduction.

Dynamic engine (host-orchestrated, paper-faithful):
    Real recursion with randomly sampled median pivots, true edge
    compaction after filtering (the linear-work claim of Theorem 1), and
    a jitted Borůvka base case on padded-to-power-of-two slices.  Used by
    the CPU benchmarks that mirror the paper's figures.

Both produce the unique MSF under the (w, edge-id) total order and are
property-tested against the Kruskal oracle and each other.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import oracle
from repro.core.boruvka import round_counters, run_rounds


# --------------------------------------------------------------------------
# Static engine
# --------------------------------------------------------------------------

def _bucket_rounds(bucket: int, n: int) -> int:
    return max(1, math.ceil(math.log2(max(min(2 * bucket, n), 2))) + 1)


@partial(jax.jit, static_argnames=("n", "num_buckets"))
def filter_boruvka_msf_counted(u: jax.Array, v: jax.Array, w: jax.Array,
                               n: int, num_buckets: int = 8
                               ) -> Tuple[jax.Array, jax.Array, dict]:
    """``filter_boruvka_msf`` and its round counters, summed over the
    buckets (``core/boruvka.py: round_counters``)."""
    m = u.shape[0]
    num_buckets = max(1, min(num_buckets, m))
    bucket = -(-m // num_buckets)
    pad = bucket * num_buckets - m
    with obs.scope("sort"):
        order = jnp.argsort(w, stable=True)  # ties broken by index: (w, idx)
        us = jnp.concatenate([u[order], jnp.zeros((pad,), u.dtype)])
        vs = jnp.concatenate([v[order], jnp.zeros((pad,), v.dtype)])
        ws = jnp.concatenate([w[order], jnp.full((pad,), jnp.inf, w.dtype)])

    labels = jnp.arange(n, dtype=jnp.int32)
    mask_sorted = jnp.zeros((num_buckets * bucket,), bool)
    counters = []

    for b in range(num_buckets):  # static schedule of quantile buckets
        sl = slice(b * bucket, (b + 1) * bucket)
        final = run_rounds(us[sl], vs[sl], ws[sl], labels,
                           jnp.zeros((bucket,), bool), n,
                           _bucket_rounds(bucket, n))
        labels = final.labels
        counters.append(round_counters(final, bucket))
        with obs.scope("sort"):
            mask_sorted = mask_sorted.at[sl].set(final.mst)

    with obs.scope("sort"):
        mask = jnp.zeros((m,), bool).at[order].set(mask_sorted[:m])
    return mask, labels, {k: [c[k] for c in counters] for k in counters[0]}


@partial(jax.jit, static_argnames=("n", "num_buckets"))
def filter_boruvka_msf(u: jax.Array, v: jax.Array, w: jax.Array, n: int,
                       num_buckets: int = 8
                       ) -> Tuple[jax.Array, jax.Array]:
    """Jittable Filter-Borůvka. Returns (mst_mask[m], labels[n])."""
    mask, labels, _ = filter_boruvka_msf_counted(u, v, w, n, num_buckets)
    return mask, labels


# --------------------------------------------------------------------------
# Dynamic engine (paper-faithful recursion with compaction)
# --------------------------------------------------------------------------

def _pad_pow2(x: np.ndarray, fill) -> np.ndarray:
    m = len(x)
    cap = 1 << max(4, math.ceil(math.log2(max(m, 1))))
    out = np.full(cap, fill, x.dtype)
    out[:m] = x
    return out


@partial(jax.jit, static_argnames=("n",))
def _base_case(u, v, w, labels, n):
    """Borůvka to completion starting from the running global labels."""
    m = u.shape[0]
    max_rounds = max(1, math.ceil(math.log2(max(min(2 * m, n), 2))) + 1)
    final = run_rounds(u, v, w, labels, jnp.zeros((m,), bool), n, max_rounds)
    return final.mst, final.labels


def filter_boruvka_dynamic(u: np.ndarray, v: np.ndarray, w: np.ndarray,
                           n: int, *, sparse_avg_degree: float = 4.0,
                           min_edges: int = 1024,
                           sample_size: int = 512,
                           seed: int = 0,
                           ) -> Tuple[np.ndarray, float]:
    """Host-driven Filter-Borůvka. Returns (mask over input edges, weight).

    Mirrors Algorithm 2: recursive median-of-sample pivoting, filtering of
    heavy edges against the partial MSF's component labels (the global
    distributed array ``P`` is the dense ``labels`` vector here), and a
    Borůvka base case once the graph is sparse (avg degree <= 4) or small.
    """
    rng = np.random.default_rng(seed)
    m = len(u)
    labels = np.arange(n, dtype=np.int32)
    mask = np.zeros(m, bool)
    mst_count = 0

    def base(eu, ev, ew, eidx):
        nonlocal labels, mst_count
        if len(eu) == 0:
            return
        pu = _pad_pow2(eu.astype(np.int32), 0)
        pv = _pad_pow2(ev.astype(np.int32), 0)
        pw = _pad_pow2(ew.astype(np.float32), np.inf)
        sub, labels_j = _base_case(jnp.asarray(pu), jnp.asarray(pv),
                                   jnp.asarray(pw), jnp.asarray(labels), n)
        sub = np.asarray(sub)[:len(eu)]
        labels = np.asarray(labels_j)
        mask[eidx[sub]] = True
        mst_count += int(sub.sum())

    def rec(eu, ev, ew, eidx):
        nonlocal labels
        n_comp = n - mst_count
        if len(eu) <= max(min_edges, sparse_avg_degree * n_comp / 2):
            base(eu, ev, ew, eidx)
            return
        # PivotSelection: median of a random sample (Section V).
        samp = rng.choice(ew, size=min(sample_size, len(ew)), replace=False)
        pivot = float(np.median(samp))
        light = ew <= pivot
        if light.all() or not light.any():  # degenerate pivot: fall back
            base(eu, ev, ew, eidx)
            return
        rec(eu[light], ev[light], ew[light], eidx[light])
        # Filter: drop heavy edges inside components of the partial MSF.
        hu, hv, hw, hidx = eu[~light], ev[~light], ew[~light], eidx[~light]
        ru, rv = labels[hu], labels[hv]
        keep = ru != rv
        # Paper Section VI-C: if filtering removed almost nothing, don't
        # recurse again immediately — just run the base case.
        survivors = (hu[keep], hv[keep], hw[keep], hidx[keep])
        rec(*survivors)

    finite = np.isfinite(w)
    rec(u[finite].astype(np.int32), v[finite].astype(np.int32),
        w[finite].astype(np.float32), np.arange(m)[finite])
    return mask, float(w[mask].sum())


def boruvka_dynamic(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int
                    ) -> Tuple[np.ndarray, float]:
    """Plain Borůvka through the dynamic-engine plumbing (for benchmarks)."""
    m = len(u)
    finite = np.isfinite(w)
    labels = np.arange(n, dtype=np.int32)
    pu = _pad_pow2(u[finite].astype(np.int32), 0)
    pv = _pad_pow2(v[finite].astype(np.int32), 0)
    pw = _pad_pow2(w[finite].astype(np.float32), np.inf)
    sub, _ = _base_case(jnp.asarray(pu), jnp.asarray(pv), jnp.asarray(pw),
                        jnp.asarray(labels), n)
    sub = np.asarray(sub)[:finite.sum()]
    mask = np.zeros(m, bool)
    mask[np.arange(m)[finite][sub]] = True
    return mask, float(w[mask].sum())


def validate_against_oracle(u, v, w, n, mask) -> bool:
    """Check a computed MSF mask against the Kruskal oracle by weight."""
    _, ow = oracle.kruskal(np.asarray(u), np.asarray(v), np.asarray(w), n)
    got = float(np.asarray(w)[np.asarray(mask)].sum())
    return abs(got - ow) < 1e-4 * max(1.0, abs(ow))
