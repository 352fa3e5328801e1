#!/usr/bin/env python3
"""Benchmark of the MSF solver on TPU chips: one run of one cell.

    python3 bench/run.py --workload kron20.boruvka --seed 7 --seconds 30 \\
        --trace 0

Makes the cell's graph from ``--seed``, warms its one program up, runs
whole solves back to back for ``--seconds``, compares every forest with
the exact reference and prints, as the last line of stdout, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics read from a
profiler trace of the window with ``--trace 1``), ``device`` and, last,
``checks``: each number compared with its limit.  The checks are also
the last lines of stderr.  Exits nonzero, printing no result, where JAX
finds no TPU, fewer chips than the cell asks for, or a device kind that
``bench/peaks.json`` does not list.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    from bench import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
