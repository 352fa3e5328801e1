"""The lexicographic sort of ``build_dist_graph`` per solve, in ms: the
program's ``msf.build.sort`` span (both directed copies of every edge,
``np.lexsort`` and its permutation), by the host clock the program keeps
for each solve record (``repro.obs``), averaged over the traced
window's solves.  Nothing to read where the program keeps no such
span."""
from bench.records import span_ms_per_solve


def read(view):
    return span_ms_per_solve(view, ("build.sort",))
