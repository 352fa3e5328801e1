"""Pointer-doubling passes per solve: the mean ``doubling_steps`` of the
program's solve records (``repro.obs``) for the traced window's solves.
Each pass is one gather over the whole parent array of CONTRACT.  The
static engines sum their passes over the rounds (Filter-Borůvka: and
over its buckets), the sharded driver those of its preprocessing
program.  Nothing to read where the program keeps no such records, or
records no such counter."""
from bench.records import window_records


def read(view):
    recs = window_records(view)
    if recs is None or any("doubling_steps" not in r for r in recs):
        return None
    return sum(r["doubling_steps"] for r in recs) / len(recs)
