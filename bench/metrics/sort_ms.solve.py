"""Device time of sort operations per solve (Filter-Borůvka's argsort,
the sharded engine's sorts, the sorts XLA adds inside the static
engine), in ms: the trace's ``sort`` events in the window, summed and
divided by the window's solves.  Nothing to read where the trace holds
no sort operation."""


def read(view):
    return view.category_ms_per_solve(("sort",))
