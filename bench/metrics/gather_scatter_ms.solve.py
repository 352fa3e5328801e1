"""Device time of gathers and scatters per solve, in ms: the events of
category ``fusion:kCustom`` (the fusions XLA's TPU backend emits for
gathers and scatters: MINEDGES' scatter-min, the label and weight
gathers, pointer doubling, the forest marking) and of any unfused
``gather`` or ``scatter``, in the window, summed and divided by the
window's solves.  Nothing to read where the trace holds none."""


def read(view):
    return view.category_ms_per_solve(("fusion:kCustom", "gather",
                                       "scatter"))
