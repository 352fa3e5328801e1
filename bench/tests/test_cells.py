"""CPU rehearsal of every cell through the harness's own functions, at a
tiny size: the timed solve, the window and the comparison.  Then the
comparison's control and the faults a cell can have, each of which has
to come out as not correct."""
import json
import time

import jax
import numpy as np
import pytest

from bench import control, harness
from bench.tests import sizes

CELLS = [w["name"] for w in harness.benchmark_spec()["workloads"]]


def tiny_run(workload, seed=3, make_solve=None, traced=False):
    cfg, slots = sizes.rehearsal(workload)
    return harness.run(
        workload, seed, 0.2, traced, time.perf_counter(),
        devices=jax.devices(), cfg_override=cfg, slots_override=slots,
        make_solve=make_solve)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearsal(workload):
    out = tiny_run(workload, seed=2**33 + 5)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    spec = harness.benchmark_spec()
    want = {m["name"] for m in harness.cell_metrics(spec, workload,
                                                    "end_to_end")}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_same_seed_same_graph():
    cfg = dict(harness.load("configs", "graph500-kron-s20"), scale=9)
    gen = harness.plugin("generators", cfg["generator"])
    a, b = gen.generate(cfg, 11), gen.generate(cfg, 11)
    c = gen.generate(cfg, 12)
    assert all(np.array_equal(x, y) for x, y in zip(a[:3], b[:3]))
    assert not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("workload", ["kron20.boruvka", "rgg20.boruvka"])
def test_control_is_not_correct(workload):
    """The reference on bfloat16 weights, in the solver's place."""
    out = tiny_run(workload, make_solve=control.make_solve)
    assert out["correct"] is False
    assert out["checks"]["wrong_edges_max"]["value"] > 0


def _state_unchanged(mask, u, slots):
    return np.zeros_like(mask)


def _answer_altered(mask, u, slots):
    out = mask.copy()
    out[len(u) // 2] = ~out[len(u) // 2]
    return out


FAULTS = {"state_unchanged": _state_unchanged,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS) + ["half_left_out"])
def test_fault_is_not_correct(workload, fault):
    """The timed path broken underneath: the run has to read false."""

    def broken(u, v, w, n, slots, params):
        if fault == "half_left_out":  # the solver sees half the edges
            w = np.where(np.arange(len(w)) < len(w) // 2, w, np.inf
                         ).astype(np.float32)
        entry = harness.plugin("entries", params["entry"])
        solve = entry.make(u, v, w, n, slots, params)

        def run(clock):
            mask, ovf = solve(clock)
            if fault in FAULTS:
                mask = FAULTS[fault](mask, u, slots)
            return mask, ovf
        return run

    out = tiny_run(workload, make_solve=broken)
    assert out["correct"] is False, out["checks"]
