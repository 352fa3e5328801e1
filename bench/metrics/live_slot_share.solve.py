"""Share of the edge slots that the rounds' MINEDGES passes read which
are still alive (endpoints in different components), in %: 100 x the
live slots summed over the rounds of the traced window's solves, over
slots times rounds, from the program's solve records (``repro.obs``).
Every per-slot gather and scatter runs over all slots, live or dead, so
this is the share of that work that can still change the forest.
Nothing to read where the program keeps no such records."""
from bench.records import window_records


def read(view):
    recs = window_records(view)
    if recs is None or any("live_slots" not in r or "slot_rounds" not in r
                           for r in recs):
        return None
    slots = sum(r["slot_rounds"] for r in recs)
    return 100.0 * sum(r["live_slots"] for r in recs) / slots \
        if slots else None
