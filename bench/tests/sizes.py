"""The CPU rehearsal's sizes.  ``data/<config>.rehearsal.json`` holds the
keys that shrink a configuration to a small instance of the same family,
which the CPU runs in seconds; a cell's rehearsal pins the slots that
the generator's ``edge_bound`` gives at that size, with room for
padding.  A new configuration adds its file."""
import json
import os

from bench import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PADDING = 1024


def shrink(config: str) -> dict:
    """The keys that shrink ``config`` for the rehearsal."""
    with open(os.path.join(DATA, f"{config}.rehearsal.json")) as f:
        return json.load(f)


def rehearsal(workload: str):
    """``(cfg_override, slots_override)`` of the cell's rehearsal."""
    cell = harness.load("workloads", workload)
    small = shrink(cell["config"])
    cfg = dict(harness.load("configs", cell["config"]), **small)
    per = harness.load("traffic", cell["traffic"]).get(
        "slots_per_edge", 1) / cell["chips"]
    bound = harness.plugin("generators", cfg["generator"]).edge_bound(cfg)
    return small, int(bound * per) + PADDING
