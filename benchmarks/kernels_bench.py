"""Kernel-layer microbenchmarks: two-phase segmented min-edge vs the
naive dense scatter (the MINEDGES hot spot), fused relabel, and the
ISSUE 8 fused owner-side scatter-min (``owner_scatter_min``) vs the jnp
scatter path it replaces.

interpret=True executes the Pallas body in Python — wall times for the
pallas paths are NOT TPU projections; the derived columns carry the
structural quantities that determine the on-device win: candidates
emitted vs edges (scatter-work reduction) for the two-phase kernel, and
materialised-intermediate bytes (compiled ``memory_analysis`` temps of
the jnp path vs the fused kernel's analytic VMEM working set) for the
scatter-min.  ``--smoke`` asserts bit-for-bit parity of the fused
kernel against the sequential oracle plus the intermediate-bytes
reduction, and runs in CI next to ``sharded_scaling --smoke``; the full
run merges a ``kernels_minedge`` section into BENCH_sharded_comm.json.
"""
from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.boruvka import min_edge_per_component
from repro.kernels.segmin.ops import min_edges_dense
from repro.kernels.segmin.ref import (EID_SENTINEL, owner_scatter_min_ref,
                                      segmin_candidates_ref)
from repro.kernels.segmin.segmin import owner_scatter_min


@functools.partial(jax.jit, static_argnames=("size",))
def _jnp_scatter_tables(idx, w, eid, pay1, pay2, ok, size: int):
    """The pre-kernel owner-side construction (the jnp comparator):
    three full-size scatter tables plus two gather-mask passes."""
    off = jnp.where(ok, idx, size)
    wmin = jnp.full((size + 1,), jnp.inf, jnp.float32).at[off].min(
        jnp.where(ok, w, jnp.inf))
    at_min = ok & (w == wmin[off])
    emin = jnp.full((size + 1,), EID_SENTINEL, jnp.int32).at[off].min(
        jnp.where(at_min, eid, EID_SENTINEL))
    is_win = at_min & (eid == emin[off])
    p1 = jnp.full((size + 1,), -1, jnp.int32).at[off].max(
        jnp.where(is_win, pay1, -1))
    p2 = jnp.full((size + 1,), -1, jnp.int32).at[off].max(
        jnp.where(is_win, pay2, -1))
    return wmin[:size], emin[:size], p1[:size], p2[:size]


def _scatter_problem(L: int, size: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, size, L).astype(np.int32))
    w = jnp.asarray(rng.integers(1, 8, L).astype(np.float32))  # ties
    eid = jnp.asarray(rng.permutation(L).astype(np.int32))
    pay1 = jnp.asarray(rng.integers(0, size, L).astype(np.int32))
    pay2 = jnp.asarray(rng.integers(0, size, L).astype(np.int32))
    ok = jnp.asarray(rng.random(L) < 0.85)
    return idx, w, eid, pay1, pay2, ok


def _temp_bytes(fn, *args) -> int | None:
    try:
        comp = jax.jit(fn).lower(*args).compile()
        return int(comp.memory_analysis().temp_size_in_bytes)
    except Exception:
        return None


def _kernel_vmem_bytes(block: int, out_block: int) -> int:
    """Analytic per-grid-step bytes of the fused kernel's blocks: five
    4-byte candidate lanes (the ok mask is folded into the index lane)
    and four 4-byte output tiles that persist across the candidate
    sweep (no [size+1] scatter tables, no full-length at_min / is_win
    masks).  Logical bytes: the TPU pads a (1, block) row to 8 sublanes
    and an (out_block, 1) column to 128 lanes, and the per-step
    [out_block, block] hit matrix is not counted."""
    return block * 5 * 4 + out_block * 4 * 4


def run_scatter_min(L: int, size: int, block: int, out_block: int,
                    smoke: bool) -> dict:
    """The ISSUE 8 microbench: fused kernel vs jnp scatter comparator,
    parity-checked bit-for-bit against the sequential oracle."""
    args = _scatter_problem(L, size)

    jnp_fn = jax.jit(lambda *a: _jnp_scatter_tables(*a, size))
    jax.block_until_ready(jnp_fn(*args))
    us_jnp = timeit(lambda: jax.block_until_ready(jnp_fn(*args)), iters=5)
    emit("kernels/minedge/owner_scatter_jnp", us_jnp,
         f"L={L};size={size}")

    fused = jax.jit(lambda *a: owner_scatter_min(
        *a, size, block=block, out_block=out_block, interpret=True))
    got = jax.block_until_ready(fused(*args))
    iters = 1 if smoke else 2
    us_fused = timeit(lambda: jax.block_until_ready(fused(*args)),
                      warmup=0, iters=iters)

    # bit-for-bit parity against both comparators (a wrong tie-break
    # here silently corrupts the MSF, so the benchmark re-proves it on
    # the exact shapes it measures)
    exp = owner_scatter_min_ref(*args, size)
    mirror = jnp_fn(*args)
    for g, e, m in zip(got, exp, mirror):
        assert np.array_equal(np.asarray(g), np.asarray(e)), \
            "fused kernel diverged from the sequential oracle"
        assert np.array_equal(np.asarray(g), np.asarray(m)), \
            "fused kernel diverged from the jnp scatter path"

    temp_jnp = _temp_bytes(lambda *a: _jnp_scatter_tables(*a, size), *args)
    vmem = _kernel_vmem_bytes(block, out_block)
    rec = {
        "L": L, "size": size, "block": block, "out_block": out_block,
        "us_jnp": us_jnp, "us_fused_interpret": us_fused,
        "jnp_temp_bytes": temp_jnp,
        "kernel_vmem_working_set_bytes": vmem,
        "parity": "bit-identical",
    }
    derived = f"L={L};size={size};parity=ok;vmem_bytes={vmem}"
    if temp_jnp:
        rec["intermediate_bytes_reduction"] = temp_jnp / max(vmem, 1)
        derived += (f";jnp_temp_bytes={temp_jnp}"
                    f";bytes_reduction={temp_jnp / max(vmem, 1):.1f}x")
    emit("kernels/minedge/pallas_fused", us_fused, derived)
    return rec


def run(smoke: bool = False) -> None:
    if smoke:
        m, n = 1 << 12, 1 << 8
        L, size, block, out_block = 1 << 12, 256, 1024, 128
    else:
        m, n = 1 << 16, 1 << 12
        L, size, block, out_block = 1 << 15, 512, 4096, 256
    rng = np.random.default_rng(0)
    seg = jnp.asarray(np.sort(rng.integers(0, n, m)).astype(np.int32))
    w = jnp.asarray(rng.uniform(1, 255, m).astype(np.float32))
    eid = jnp.arange(m, dtype=jnp.int32)
    alive = jnp.asarray(rng.random(m) < 0.9)

    naive = jax.jit(lambda: min_edge_per_component(seg, seg, w, n))
    jax.block_until_ready(naive())
    us_naive = timeit(lambda: jax.block_until_ready(naive()), iters=5)
    emit("kernels/minedge/naive_scatter", us_naive, f"m={m};n={n}")

    twophase = jax.jit(lambda: min_edges_dense(seg, w, eid, alive, n,
                                               use_pallas=False))
    jax.block_until_ready(twophase())
    us_two = timeit(lambda: jax.block_until_ready(twophase()), iters=5)
    cw, _ = segmin_candidates_ref(seg, w, eid, alive)
    cand = int(jnp.isfinite(cw).sum())
    emit("kernels/minedge/two_phase_jnp", us_two,
         f"candidates={cand};scatter_reduction={m / max(cand, 1):.1f}x")

    pallas = jax.jit(lambda: min_edges_dense(seg, w, eid, alive, n,
                                             use_pallas=True,
                                             interpret=True))
    jax.block_until_ready(pallas())
    us_p = timeit(lambda: jax.block_until_ready(pallas()),
                  warmup=0, iters=1 if smoke else 2)
    emit("kernels/minedge/pallas_interpret", us_p,
         "interpret-mode;not-a-TPU-projection")

    rec = run_scatter_min(L, size, block, out_block, smoke)

    if smoke:
        # CI acceptance (ISSUE 8): parity is asserted inside
        # run_scatter_min; the fused kernel's working set must
        # materialise fewer intermediate bytes than the jnp scatter
        # path's compiled temps (skip only if the backend exposes no
        # memory_analysis), and interpret-mode wall time only bounds
        # very loosely (the Python-interpreted body is not a projection)
        red = rec.get("intermediate_bytes_reduction")
        assert red is None or red > 1.0, rec
        assert rec["us_fused_interpret"] < 600e6, rec
        return
    path = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "BENCH_sharded_comm.json"))
    bench = {}
    if os.path.exists(path):
        with open(path) as f:
            bench = json.load(f)
    bench["kernels_minedge"] = {f"scatter/L={L}": rec}
    with open(path, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)


if __name__ == "__main__":
    run(smoke="--smoke" in sys.argv[1:])
    print("kernels_bench: OK")
