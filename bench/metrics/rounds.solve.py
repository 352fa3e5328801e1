"""Borůvka rounds per solve: the mean ``rounds`` of the program's solve
records (``repro.obs``) for the traced window's solves.  The static
engines count their ``while_loop`` iterations (Filter-Borůvka: summed
over its buckets), the sharded driver its preprocessing rounds plus the
round steps it dispatched.  Nothing to read where the program keeps no
such records."""
from bench.records import window_records


def read(view):
    recs = window_records(view)
    if recs is None or any("rounds" not in r for r in recs):
        return None
    return sum(r["rounds"] for r in recs) / len(recs)
