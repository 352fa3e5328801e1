"""Public MSF API.

``minimum_spanning_forest`` dispatches between:
  * algorithm: "boruvka" (Section IV) | "filter_boruvka" (Section V)
  * engine: "static" (fully jittable) | "dynamic" (host-orchestrated
    recursion with compaction) | "distributed" (shard_map over a device
    mesh, replicated labels; see core/distributed.py) |
    "distributed_sharded" (shard_map with 1D-sharded labels and routed
    label exchange, the paper's scalable path; see
    core/distributed_sharded.py and EXPERIMENTS.md §Sharded-label engine)

Mesh-engine knobs pass through ``**kw``: ``axis_names``, ``max_rounds``,
``local_preprocessing``, and for the sharded engine the capacity knobs
(``edge_capacity`` / ``label_capacity`` / ``lookup_capacity`` /
``push_capacity`` — explicit undersized values surface as the overflow
error below), the comm levers (``coalesce``, ``src_only``,
``adaptive_doubling``, ``ghost_cache``, ``relabel_skip``), and
``shrink_capacities`` (default on: per-round shrinking exchange
capacities from host bounds on the dead-edge mask; pass False for the
fused flat-capacity program, e.g. to compare counters).  ``ghost_cache``
(default on) replaces the per-round endpoint lookups with per-shard
ghost-label tables maintained by a dirty-label push from the owners —
see core/distributed_sharded.py.  ``plan`` (ISSUE 5) replays a measured
``core/plan.py: RoundPlan`` as one Python-unrolled program — the
shrinking schedule without the host in the loop, AOT-lowerable; an
ill-fitting plan replans, never silently degrades (see
docs/ARCHITECTURE.md §Round plans).  The engine matrix with
when-to-use guidance is in README.md; docs/ARCHITECTURE.md maps the
knobs to the paper's phases.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.boruvka import boruvka_msf_counted
from repro.core.filter_boruvka import (boruvka_dynamic, filter_boruvka_dynamic,
                                       filter_boruvka_msf_counted)
from repro.core.graph import EdgeList


def _distributed_dispatch(edges: EdgeList, mesh: jax.sharding.Mesh,
                          engine: str, algorithm: str,
                          **kw) -> Tuple[jax.Array, jax.Array]:
    """Bridge the single-array public API onto the mesh engines.

    Host-side: drop padding, double + sort + 1D-partition the edges
    (the engines' on-PE input format), run, then reduce the slot mask
    back to the caller's edge positions via the undirected edge ids.
    The rebuild is O(m log m) numpy work *per call*; repeated solves of
    the same graph should build a ``DistGraph`` once and call
    ``distributed_msf`` / ``distributed_sharded_msf`` directly (those
    cache their compiled programs).
    """
    from repro.core.distributed import build_dist_graph, distributed_msf
    from repro.core.distributed_sharded import distributed_sharded_msf

    u = np.asarray(edges.u)
    v = np.asarray(edges.v)
    w = np.asarray(edges.w)
    idx = np.nonzero(np.isfinite(w))[0]
    axes = tuple(kw.get("axis_names") or mesh.axis_names)
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    g, _ = build_dist_graph(u[idx], v[idx], w[idx], edges.n, p)
    run = (distributed_msf if engine == "distributed"
           else distributed_sharded_msf)
    res = run(g, edges.n, mesh, algorithm=algorithm, **kw)
    # res: (mask, weight, count, labels, stats) for distributed, plus an
    # overflow count at [4] (stats moves to [5]) for distributed_sharded
    mask_slots = np.asarray(res[0])
    if engine == "distributed_sharded":
        overflow = int(res[4])
        if overflow:  # hard error, not assert: must survive python -O
            raise RuntimeError(
                f"exchange overflow ({overflow} items): retry with larger "
                "edge_capacity/label_capacity")
    sel = np.unique(np.asarray(g.eid)[mask_slots])
    out = np.zeros(edges.m, bool)
    out[idx[sel]] = True
    return jnp.asarray(out), res[1]


def minimum_spanning_forest(edges: EdgeList, *, algorithm: str = "boruvka",
                            engine: str = "static",
                            num_buckets: Optional[int] = None,
                            mesh: Optional[jax.sharding.Mesh] = None,
                            **kw) -> Tuple[jax.Array, jax.Array]:
    """Compute an MSF. Returns (mask over edges, total weight).

    ``num_buckets`` controls filter_boruvka's weight bucketing; each
    engine keeps its own default when it is not given (static: 8,
    distributed engines: 4 levels).

    The static engine, and the sharded one through
    ``distributed_sharded_msf``, leave one record of round counters per
    solve in ``repro.obs``.
    """
    if num_buckets is not None and num_buckets < 1:
        raise ValueError(f"num_buckets must be >= 1, got {num_buckets}")
    if engine in ("distributed", "distributed_sharded"):
        if mesh is None:  # hard error, not assert: must survive python -O
            raise ValueError(f"{engine} engine needs a mesh")
        if num_buckets is not None:
            # the mesh engines call their filter knob num_levels
            kw.setdefault("num_levels", num_buckets)
        return _distributed_dispatch(edges, mesh, engine, algorithm, **kw)
    if engine == "static":
        obs.begin()
        if algorithm == "boruvka":
            mask, _, counters = boruvka_msf_counted(edges.u, edges.v, edges.w,
                                                    edges.n)
        elif algorithm == "filter_boruvka":
            mask, _, counters = filter_boruvka_msf_counted(
                edges.u, edges.v, edges.w, edges.n,
                num_buckets=8 if num_buckets is None else num_buckets)
        else:
            raise ValueError(algorithm)
        weight = jnp.sum(jnp.where(mask & edges.valid, edges.w, 0.0))
        obs.record(**counters)
        return mask, weight
    if engine == "dynamic":
        u = np.asarray(edges.u)
        v = np.asarray(edges.v)
        w = np.asarray(edges.w)
        if algorithm == "boruvka":
            mask, wt = boruvka_dynamic(u, v, w, edges.n)
        elif algorithm == "filter_boruvka":
            mask, wt = filter_boruvka_dynamic(u, v, w, edges.n, **kw)
        else:
            raise ValueError(algorithm)
        return jnp.asarray(mask), jnp.asarray(wt, jnp.float32)
    raise ValueError(engine)
