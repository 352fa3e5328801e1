"""Quickstart: compute an MSF with every engine on a generated graph.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.compile_cache import place_compile_cache
from repro.core import oracle
from repro.core.graph import from_numpy
from repro.core.mst import minimum_spanning_forest
from repro.data import generators


def main() -> None:
    place_compile_cache()
    u, v, w, n = generators.generate("rgg2d", 2048, avg_degree=8.0, seed=0)
    print(f"graph: rgg2d n={n} m={len(u)}")
    edges = from_numpy(u, v, w, n)
    _, expect = oracle.kruskal(u, v, w, n)
    print(f"oracle (Kruskal) MSF weight: {expect:.1f}")
    for algo in ("boruvka", "filter_boruvka"):
        for engine in ("static", "dynamic"):
            mask, wt = minimum_spanning_forest(edges, algorithm=algo,
                                               engine=engine)
            status = "OK" if abs(float(wt) - expect) < 1e-3 * expect \
                else "MISMATCH"
            print(f"  {algo:16s} engine={engine:8s} weight={float(wt):12.1f}"
                  f"  edges={int(np.asarray(mask).sum()):6d}  [{status}]")


if __name__ == "__main__":
    main()
