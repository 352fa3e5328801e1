#!/usr/bin/env python3
"""Smoke run of the MSF engines on TPU chips, through their entry points.

    python3 chip_smoke.py               # phases A-D on one chip
    python3 chip_smoke.py --four-chips  # the mesh engines on four chips

Every phase solves a generated graph (data/generators.py, from
``--seed``) and checks the forest against the exact (w, eid) Kruskal
edge set of ``core/oracle.py: kruskal_fast`` on the host: equality of
edge sets, not just of weights (weights are float32 on [1, 255), so ties
are common).  One chip:

  A. static engine, ``algorithm="boruvka"`` and ``"filter_boruvka"``,
     on the Graph500 Kronecker graph at scale 20, edgefactor 16;
  B. sharded engine on a one-chip mesh: the host-driven shrinking path,
     then ``plan_sharded_msf`` + strict ``execute_plan`` replay, on
     rgg2d at n=2^20 (overflow 0, no replan);
  C. the fused Pallas MINEDGES kernel (``pallas_minedges=True``) against
     the jnp path, bit for bit, on rgg2d at n=2^12 with routed rounds;
  D. the serving gateway on the one-chip mesh: 8 requests of a gnm and
     rgg2d mix at n=2^16 in two waves, 4 batch slots.

C is cut in size: the kernel's grid is (table tiles x candidate
blocks), so at n=2^20 one routed round would take about 5 x 10^8 grid
steps.

Four chips: gnm at n=2^16, avg degree 8, on a 4-chip mesh: the sharded
engine host-driven and by planned replay, then the replicated engine.
Right after the sharded solves, before the replicated engine puts the
whole graph on every chip, every device's peak memory must be at least
half the mean.  The size is set by cold compilation: every round
capacity of the host-driven path and the planned replay are programs of
their own (``tools/rehearse_chip_smoke.py`` sums them; PERF.md).

Per phase it prints the graph, the first call's time (compilation plus
one solve: set-up), a warm solve's time through ``block_until_ready``
(a smoke time, not a benchmark) and each device's
``peak_bytes_in_use``.  Any fault raises and exits nonzero.  The last
line of stdout is one JSON object naming the device.  The script needs
a TPU: on any other platform it exits nonzero before the first phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def report(phase: str, devices, cold: float, warm: float) -> None:
    peaks = [peak_bytes(d) for d in devices]
    log(f"  {phase}: first call (compile + solve, set-up) {cold} s, "
        f"warm solve (smoke time) {warm} s, peak_bytes_in_use "
        f"{peaks}")


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


def graph_line(family: str, u, n: int, extra: str = "") -> None:
    log(f"graph {family}: n={n} m={len(u)}{extra}")


def oracle_mask(u, v, w, n):
    from repro.core import oracle
    t = time.perf_counter()
    want = oracle.kruskal_fast(u, v, w, n)
    log(f"  oracle (host, kruskal_fast): {int(want.sum())} forest edges "
        f"in {time.perf_counter() - t} s")
    return want


def same_forest(mask, want, what: str) -> None:
    got = np.asarray(mask)[:len(want)]
    require(np.array_equal(got, want),
            f"{what}: forest differs from the (w, eid) oracle "
            f"({int(got.sum())} vs {int(want.sum())} edges)")
    log(f"  {what}: oracle-identical ({int(want.sum())} edges)")


def slot_mask_to_edges(g, mask_slots, m: int) -> np.ndarray:
    out = np.zeros(m, bool)
    out[np.unique(np.asarray(g.eid)[np.asarray(mask_slots)])] = True
    return out


def phase_static(seed: int, devices, scale: int = 20,
                 edgefactor: int = 16) -> None:
    from repro.core.graph import from_numpy
    from repro.core.mst import minimum_spanning_forest
    from repro.data import generators
    t = time.perf_counter()
    u, v, w, n = generators.rmat(scale, edgefactor << scale, seed)
    graph_line("rmat (Graph500 Kronecker)", u, n,
               f" scale={scale} edgefactor={edgefactor}, generated in "
               f"{time.perf_counter() - t} s")
    want = oracle_mask(u, v, w, n)
    edges = from_numpy(u, v, w, n)
    for algo in ("boruvka", "filter_boruvka"):
        def solve():
            return minimum_spanning_forest(edges, algorithm=algo)
        _, cold = timed(solve)
        (mask, _), warm = timed(solve)
        report(f"A static {algo}", devices, cold, warm)
        same_forest(mask, want, f"A static {algo}")


def sharded_solves(u, v, w, n, want, mesh, devices, tag: str) -> None:
    """Host-driven shrinking path through the public API, then a measured
    plan replayed strictly (a misfit raises instead of replanning)."""
    from repro.core.distributed import build_dist_graph
    from repro.core.distributed_sharded import (execute_plan,
                                                plan_sharded_msf)
    from repro.core.graph import from_numpy
    from repro.core.mst import minimum_spanning_forest
    edges = from_numpy(u, v, w, n)

    def host_driven():
        return minimum_spanning_forest(edges, engine="distributed_sharded",
                                       mesh=mesh)
    _, cold = timed(host_driven)
    (mask, _), warm = timed(host_driven)
    report(f"{tag} sharded host-driven", devices, cold, warm)
    same_forest(mask, want, f"{tag} sharded host-driven")

    p = len(mesh.devices.flat)
    g, _ = build_dist_graph(u, v, w, n, p)
    t = time.perf_counter()
    plan = plan_sharded_msf(g, n, mesh)
    log(f"  {tag} plan_sharded_msf: {plan.num_rounds} rounds measured in "
        f"{time.perf_counter() - t} s")

    def replay():
        return execute_plan(g, n, mesh, plan, replan=False)
    _, cold = timed(replay)
    res, warm = timed(replay)
    require(int(res[4]) == 0, f"{tag} planned replay overflow {int(res[4])}")
    report(f"{tag} sharded planned replay (overflow 0, no replan)",
           devices, cold, warm)
    same_forest(slot_mask_to_edges(g, res[0], len(u)), want,
                f"{tag} sharded planned replay")


def phase_sharded(seed: int, devices, n: int = 1 << 20) -> None:
    from jax.sharding import Mesh
    from repro.data import generators
    t = time.perf_counter()
    u, v, w, n = generators.rgg2d(n, 8.0, seed)
    graph_line("rgg2d", u, n, f" avg_degree=8, generated in "
               f"{time.perf_counter() - t} s")
    mesh = Mesh(np.array(devices[:1]), ("data",))
    sharded_solves(u, v, w, n, oracle_mask(u, v, w, n), mesh, devices[:1],
                   "B")


def phase_pallas(seed: int, devices, n: int = 1 << 12) -> None:
    """The kernel runs only in routed rounds.  On one chip local
    preprocessing contracts every edge before the first round, so both
    paths turn it off here, and run the fused flat-capacity program:
    every round's MINEDGES goes through the kernel, in one compile."""
    from jax.sharding import Mesh
    from repro.core.distributed import build_dist_graph
    from repro.core.distributed_sharded import distributed_sharded_msf
    from repro.data import generators
    u, v, w, n = generators.rgg2d(n, 8.0, seed)
    graph_line("rgg2d", u, n, " avg_degree=8, local_preprocessing=False, "
               "flat capacities")
    want = oracle_mask(u, v, w, n)
    mesh = Mesh(np.array(devices[:1]), ("data",))
    g, _ = build_dist_graph(u, v, w, n, 1)
    masks = {}
    for pallas in (False, True):
        def solve():
            return distributed_sharded_msf(
                g, n, mesh, local_preprocessing=False,
                shrink_capacities=False, pallas_minedges=pallas)
        _, cold = timed(solve)
        res, warm = timed(solve)
        rounds = int(res[5].rounds)
        require(int(res[4]) == 0, f"C overflow {int(res[4])}")
        require(rounds > 0, "C ran no routed round")
        name = f"C pallas_minedges={pallas}"
        report(f"{name} ({rounds} routed rounds)", devices[:1], cold, warm)
        masks[pallas] = np.asarray(res[0])
        same_forest(slot_mask_to_edges(g, res[0], len(u)), want, name)
    require(np.array_equal(masks[False], masks[True]),
            "C Pallas slot mask differs from the jnp path")
    log("  C kernel vs jnp: slot masks bit-identical")


def phase_gateway(seed: int, devices, n: int = 1 << 16,
                  requests: int = 8) -> None:
    from jax.sharding import Mesh
    from repro.launch.serve_msf import make_traffic
    from repro.serve.msf_gateway import MSFGateway
    mesh = Mesh(np.array(devices[:1]), ("data",))
    reqs = make_traffic(["gnm", "rgg2d"], [n], requests, seed=seed)
    log(f"graph gateway mix: {requests} requests of gnm/rgg2d at n={n}, "
        f"m={[len(r.u) for r in reqs]}")
    gw = MSFGateway(mesh, batch_slots=4)
    t = time.perf_counter()
    # two waves: the second finds the plans the first measured
    for wave in (reqs[:requests // 2], reqs[requests // 2:]):
        for r in wave:
            gw.submit(r)
        gw.run()
    dt = time.perf_counter() - t
    s = gw.stats
    log(f"  D gateway: {len(reqs)} requests in {dt} s (compile "
        f"included), {s.batches} dispatches, {s.hits} hits / {s.misses} "
        f"misses, {s.replans} replans, {s.rejected} rejected, "
        f"peak_bytes_in_use {peak_bytes(devices[0])}")
    require(all(r.done for r in reqs), "D a request was not done")
    require(s.rejected == 0, f"D gateway rejected {s.rejected}")
    require(s.hits >= 1, "D no plan-cache hit")
    require(any(r.served_via == "batched" for r in reqs)
            and s.served > s.batches, "D no batched dispatch")
    from repro.core import oracle
    for r in reqs:
        want = np.nonzero(oracle.kruskal_fast(r.u, r.v, r.w, r.n))[0]
        require(np.array_equal(r.edges, want),
                f"D request {r.rid}: forest differs from the oracle")
    log(f"  D gateway: {len(reqs)} forests oracle-identical")


def phase_four_chips(seed: int, devices, n: int = 1 << 16) -> None:
    from jax.sharding import Mesh
    from repro.core.graph import from_numpy
    from repro.core.mst import minimum_spanning_forest
    from repro.data import generators
    t = time.perf_counter()
    u, v, w, n = generators.gnm(n, 4 * n, seed)
    graph_line("gnm", u, n, f" avg_degree=8, generated in "
               f"{time.perf_counter() - t} s")
    mesh = Mesh(np.array(devices[:4]), ("data",))
    want = oracle_mask(u, v, w, n)
    sharded_solves(u, v, w, n, want, mesh, devices[:4], "4-chip")
    # read before the replicated engine puts the whole graph on every chip
    peaks = np.array([peak_bytes(d) for d in devices[:4]], np.float64)
    require(peaks.min() >= 0.5 * peaks.mean(),
            f"sharded engine: a device's peak is under half the mean: "
            f"{peaks.tolist()}")
    log(f"  4-chip sharded engine: every device's peak is at least half "
        f"the mean ({peaks.tolist()})")
    edges = from_numpy(u, v, w, n)

    def replicated():
        return minimum_spanning_forest(edges, engine="distributed",
                                       mesh=mesh)
    _, cold = timed(replicated)
    (mask, _), warm = timed(replicated)
    report("4-chip replicated distributed", devices[:4], cold, warm)
    same_forest(mask, want, "4-chip replicated distributed")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh engines, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke.py --four-chips needs 4 chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.compile_cache import place_compile_cache
    log(f"device {devices[0].device_kind} x{len(devices)}, compilation "
        f"cache {place_compile_cache()}")
    t0 = time.perf_counter()
    phases = ([phase_four_chips] if args.four_chips else
              [phase_static, phase_sharded, phase_pallas, phase_gateway])
    for phase in phases:
        phase(args.seed, devices)
        log(f"{phase.__name__} done, {time.perf_counter() - t0} s since "
            f"the first phase began")
    log(f"all phases passed in {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
