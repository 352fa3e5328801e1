"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Figure mapping:
    weak_scaling   -> Fig. 3 (six graph families, boruvka vs filter)
    alltoall       -> Fig. 2 (two-level grid vs direct all-to-all)
    preprocessing  -> Fig. 4 (local contraction on/off)
    strong_scaling -> Fig. 5 (fixed graph, growing p)
    phases         -> Fig. 6 (per-phase time distribution)
    kernels_bench  -> kernel-layer microbenches (MINEDGES hot spot)
"""
from __future__ import annotations


def main() -> None:
    print("name,us_per_call,derived")
    from benchmarks import (alltoall, kernels_bench, phases, preprocessing,
                            sharded_scaling, strong_scaling, weak_scaling)
    # a module that crashes ends the run with a nonzero exit
    for mod in (weak_scaling, alltoall, preprocessing, strong_scaling,
                sharded_scaling, phases, kernels_bench):
        mod.run()


if __name__ == "__main__":
    main()
