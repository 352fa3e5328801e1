"""Host work of the sharded driver per solve, in ms: the program's
``msf.driver.{lookup_bound,readback,index,ghost_bounds,bounds,finish}``
spans (the default lookup capacity, the edge read-back, run heads and
v-permutation, ghost table bounds, the per-level and per-round capacity
bounds, and the result's assembly), by the host clock the program keeps
for each solve record (``repro.obs``), averaged over the traced
window's solves.  The spans that wait on a device program
(``driver.prep``, ``driver.ghost_setup``, ``driver.step``) are left
out.  Nothing to read where the program keeps no such spans."""
from bench.records import span_ms_per_solve

SPANS = ("driver.lookup_bound", "driver.readback", "driver.index",
         "driver.ghost_bounds", "driver.bounds", "driver.finish")


def read(view):
    return span_ms_per_solve(view, SPANS)
