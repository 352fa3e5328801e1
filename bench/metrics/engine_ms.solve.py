"""Engine program per solve: the harness's span from the engine call to
the forest mask on the host (through ``block_until_ready``; for the
sharded entry also the reduction of slots to edges by ``eid``), in ms
by the host clock, averaged over the traced window's solves."""


def read(view):
    return view.span_mean_ms("engine")
