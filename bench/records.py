"""The program's own solve records (``repro.obs``) for a traced window:
what the ``program_counter`` and ``program_span`` readers read."""
from __future__ import annotations

from typing import List, Optional


def window_records(view) -> Optional[List[dict]]:
    """One record per solve of the traced window, oldest first; None
    where the program keeps no records, or fewer than the window's
    solves."""
    try:
        from repro import obs
    except ImportError:
        return None
    recs = obs.solve_records(last=view.solves)
    return recs if recs and len(recs) == view.solves else None


def span_ms_per_solve(view, names) -> Optional[float]:
    """The program's host seconds under these span names, in ms per
    solve of the window; None where some solve has none of them."""
    recs = window_records(view)
    if recs is None or not all(any(n in r["host_s"] for n in names)
                               for r in recs):
        return None
    return 1e3 * sum(r["host_s"].get(n, 0.0)
                     for r in recs for n in names) / len(recs)
