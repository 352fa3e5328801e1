"""Host preparation per solve: the harness's span around the call that
turns host edge arrays into the engine's device input (``from_numpy``
for the static entry, ``build_dist_graph`` for the sharded one), in ms
by the host clock, averaged over the traced window's solves."""


def read(view):
    return view.span_mean_ms("host_prep")
