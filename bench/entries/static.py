"""The static entry (``"entry": "static"``): ``core/graph.py:
from_numpy`` padded to the cell's pinned slot count, then ``core/mst.py:
minimum_spanning_forest`` on the one chip, with the traffic's
``algorithm`` and the engine's default options.

An entry module gives ``make(u, v, w, n, slots, params) -> solve``; the
harness loads it by the name the traffic file gives.  A solve starts
from the user's host edge arrays and ends with the forest as a host mask
over those edges (here with the padding slots), through
``block_until_ready``; ``solve(clock)`` returns ``(mask, overflow)`` and
records the ``host_prep`` and ``engine`` spans of every call.
"""
from __future__ import annotations


def make(u, v, w, n: int, slots: int, params: dict):
    import jax
    import numpy as np
    from repro.core.graph import from_numpy
    from repro.core.mst import minimum_spanning_forest

    algorithm = params["algorithm"]

    def solve(clock):
        with clock.span("host_prep"):
            edges = from_numpy(u, v, w, n, pad_to=slots)
        with clock.span("engine"):
            mask, _ = jax.block_until_ready(minimum_spanning_forest(
                edges, algorithm=algorithm))
            mask = np.asarray(mask)
        return mask, 0

    return solve
