"""End-to-end driver (the paper's kind of workload): distributed MSF on a
device mesh — generate, 1D-partition, run Borůvka + Filter-Borůvka with
local preprocessing, validate against the oracle, report throughput.

Re-executes itself with 8 virtual devices if only one is present:

    PYTHONPATH=src python examples/distributed_mst.py [--family rmat]
"""
import argparse
import os
import sys
import time

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.compile_cache import place_compile_cache  # noqa: E402
from repro.core import oracle  # noqa: E402
from repro.core.distributed import build_dist_graph, distributed_msf  # noqa: E402
from repro.core.distributed_sharded import distributed_sharded_msf  # noqa: E402
from repro.data import generators  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="rmat",
                    choices=list(generators.FAMILIES))
    ap.add_argument("--n", type=int, default=1 << 13)
    ap.add_argument("--degree", type=float, default=16.0)
    args = ap.parse_args()

    place_compile_cache()
    p = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    print(f"devices: {p}  family: {args.family}")

    u, v, w, n = generators.generate(args.family, args.n, args.degree,
                                     seed=7)
    g, cap = build_dist_graph(u, v, w, n, p)
    print(f"graph: n={n} undirected_m={len(u)} slots/shard={cap}")
    _, expect = oracle.kruskal(u, v, w, n)

    def solve(label, runner):
        t0 = time.perf_counter()
        out = runner()
        jax.block_until_ready(out[0])
        compile_run = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = runner()
        jax.block_until_ready(out[0])
        run = time.perf_counter() - t0
        wt, cnt = out[1], out[2]
        stats = out[-1]  # CommStats, last element for both engines
        rounds = max(int(stats.rounds), 1)
        ok = abs(float(wt) - expect) < 1e-3 * max(expect, 1.0)
        print(f"  {label:26s} weight={float(wt):14.1f} edges={int(cnt):7d} "
              f"[{'OK' if ok else 'MISMATCH'}] "
              f"first={compile_run:.2f}s steady={run:.3f}s "
              f"({2 * len(u) / run / 1e6:.2f} Medges/s)")
        print(f"  {'':26s} comm: {int(stats.calls)} collectives over "
              f"{int(stats.rounds)} rounds "
              f"({int(stats.calls) / rounds:.1f}/round), "
              f"{float(stats.items) / 1e3:.1f}k items, "
              f"{float(stats.bytes) / 1e6:.2f} MB")

    for algo in ("boruvka", "filter_boruvka"):
        solve(algo, lambda: distributed_msf(
            g, n, mesh, algorithm=algo, axis_names=("data",)))
        # the sharded-label engine: O(n/p) label memory per device,
        # routed label exchange instead of dense allreduce
        solve(f"{algo}+sharded_labels", lambda: distributed_sharded_msf(
            g, n, mesh, algorithm=algo, axis_names=("data",)))


if __name__ == "__main__":
    main()
