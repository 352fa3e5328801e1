#!/usr/bin/env python3
"""The control of the comparison: the reference in bfloat16, in the
solver's place, has to come out as not correct.

The configurations state float32 weights and the exact ``(w, eid)``
forest.  The control solves each window's graph with the reference's
own algorithm on the weights rounded to bfloat16, the nearest precision
below, so ties and order change as a cheaper solver's would; the
harness's comparison then has to find the forest wrong.

    python3 bench/control.py --workload kron20.boruvka --seeds 1 2 3

runs one short window per seed on the machine it is started on (a TPU,
like the benchmark itself) and prints one JSON line per seed with the
numbers compared.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def bf16(w: np.ndarray) -> np.ndarray:
    """float32 weights rounded to the nearest bfloat16, back in float32."""
    import ml_dtypes
    return w.astype(ml_dtypes.bfloat16).astype(np.float32)


def make_solve(u, v, w, n, slots, params):
    """A solve that returns the bfloat16 reference's forest."""
    from bench import reference
    wl = bf16(w)

    def solve(clock):
        return reference.msf_mask(u, v, wl, n), 0

    return solve


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    from bench import harness
    for seed in args.seeds:
        out = harness.run(args.workload, seed, args.seconds, False,
                          time.perf_counter(), make_solve=make_solve)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
