"""Program-side tracing (``repro.obs``): phase scopes in the compiled
programs, round counters against a NumPy recount, host spans in a
profiler trace."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.core import distributed_sharded as ds
from repro.core.boruvka import boruvka_msf_counted
from repro.core.distributed import build_dist_graph
from repro.core.filter_boruvka import (_bucket_rounds,
                                       filter_boruvka_msf_counted)
from repro.core.graph import EdgeList, from_numpy
from repro.core.mst import minimum_spanning_forest

N = 96
PROGRAM_FNS = {name: getattr(ds, name) for name in (
    "_build_sharded_prep_fn", "_build_ghost_setup_fn",
    "_build_sharded_round_fn")}


def _graph(seed=0, n=N, m=300):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    keep = u != v
    u, v = u[keep].astype(np.int32), v[keep].astype(np.int32)
    # few distinct weights: ties go to the (w, edge id) order
    w = rng.integers(1, 6, len(u)).astype(np.float32)
    return u, v, w


def _recount(u, v, w, comp, max_rounds):
    """Borůvka rounds over the slots (u, v, w) from the component labels
    ``comp`` (updated in place): the alive slots of every round run, the
    last one being the round that found nothing to contract, and the
    pointer-doubling passes of each round's CONTRACT.  A component points
    at the other end of its (w, slot)-least edge; of a 2-cycle the
    smaller label is the root; doubling stops after the first pass that
    changes nothing, or after ceil(log2 n) passes."""
    n = len(comp)
    ids = np.arange(n)
    live, steps = [], []
    while len(live) < max_rounds:
        alive = comp[u] != comp[v]
        live.append(int(alive.sum()))
        best = {}
        for i in np.nonzero(alive)[0]:
            for c in (comp[u[i]], comp[v[i]]):
                if c not in best or (w[i], i) < (w[best[c]], best[c]):
                    best[c] = i
        par = ids.copy()
        for c, i in best.items():
            par[c] = comp[u[i]] + comp[v[i]] - c
        par = np.where((par[par] == ids) & (ids < par), ids, par)
        k = 0
        while k < max(1, int(np.ceil(np.log2(max(n, 2))))):
            k += 1
            if (par[par] == par).all():
                break
            par = par[par]
        steps.append(k)
        comp[:] = par[comp]
        if not alive.any():
            break
    return live, steps


def _max_rounds(n, m):
    return max(1, int(np.ceil(np.log2(max(min(n, 2 * m), 2)))) + 1)


def test_static_counters_match_recount():
    u, v, w = _graph()
    obs.clear()
    e = from_numpy(u, v, w, N, pad_to=512)
    minimum_spanning_forest(e)
    (rec,) = obs.solve_records()
    uu, vv, ww = (np.asarray(x) for x in (e.u, e.v, e.w))
    live, steps = _recount(uu, vv, ww, np.arange(N), _max_rounds(N, 512))
    assert rec["rounds"] == len(live) > 2
    assert rec["live_slots"] == sum(live)
    assert rec["slot_rounds"] == 512 * len(live)
    assert rec["doubling_steps"] == sum(steps) > len(live)
    assert rec["host_s"]["pack"] > 0


def test_filter_counters_match_recount():
    u, v, w = _graph(1)
    e = from_numpy(u, v, w, N, pad_to=512)
    obs.clear()
    minimum_spanning_forest(e, algorithm="filter_boruvka", num_buckets=4)
    (rec,) = obs.solve_records()
    uu, vv, ww = (np.asarray(x) for x in (e.u, e.v, e.w))
    order = np.argsort(ww, kind="stable")
    comp = np.arange(N)
    rounds = live = steps = 0
    for b in range(4):
        sl = order[b * 128:(b + 1) * 128]
        got, k = _recount(uu[sl], vv[sl], ww[sl], comp,
                          _bucket_rounds(128, N))
        rounds += len(got)
        live += sum(got)
        steps += sum(k)
    assert (rec["rounds"], rec["live_slots"], rec["slot_rounds"],
            rec["doubling_steps"]) == (rounds, live, 128 * rounds, steps)


@pytest.mark.parametrize("prep", [True, False])
def test_sharded_counters_match_recount(prep):
    u, v, w = _graph(2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    trace = []
    obs.clear()
    g, cap = build_dist_graph(u, v, w, N, 1, cap=640)
    ds.distributed_sharded_msf(g, N, mesh, local_preprocessing=prep,
                               round_trace=trace)
    (rec,) = obs.solve_records()
    gu, gv, gw, ge = (np.asarray(x) for x in g)
    # the (w, eid) order, both directed copies of an edge alike
    key = gw.astype(np.float64) * 1e6 + np.where(np.isfinite(gw), ge, 0)
    live, steps = _recount(gu, gv, key, np.arange(N), 64)
    if prep:
        # at p=1 preprocessing contracts every edge, ending on an empty
        # round; no round step runs after it
        assert trace == []
        assert rec["rounds"] == len(live)
        assert rec["doubling_steps"] == sum(steps)
    else:
        # the driver skips the trailing round whose host bound is zero;
        # the routed rounds' doubling is not counted
        assert rec["rounds"] == len(live) - 1 == len(trace)
        assert rec["doubling_steps"] == 0
    assert rec["live_slots"] == sum(live)
    assert rec["slot_rounds"] == cap * rec["rounds"]
    spans = set(rec["host_s"])
    assert {"build.sort", "build.pack", "driver.lookup_bound",
            "driver.readback", "driver.index", "driver.bounds",
            "driver.finish"} <= spans
    assert ("driver.prep" in spans) == prep
    assert ("driver.step" in spans) == (not prep)


def test_spans_outside_a_solve_are_not_charged():
    u, v, w = _graph(7)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    obs.clear()
    g, _ = build_dist_graph(u, v, w, N, 1)
    ds.plan_sharded_msf(g, N, mesh)
    e = from_numpy(u, v, w, N, pad_to=512)
    with obs.span("stray"):
        pass
    minimum_spanning_forest(e)
    g2, _ = build_dist_graph(u, v, w, N, 1)
    with obs.span("stray"):
        pass
    ds.distributed_sharded_msf(g2, N, mesh)
    plan, static, sharded = (set(r["host_s"])
                             for r in obs.solve_records())
    assert {"build.sort", "driver.lookup_bound"} <= plan
    assert "pack" not in plan
    assert static == {"pack"}
    assert {"build.sort", "build.pack", "driver.lookup_bound",
            "driver.finish"} <= sharded
    assert not {"pack", "stray"} & sharded


def test_fig6_phases_benchmark_runs(capsys):
    from benchmarks import phases
    phases.run(n=1 << 8)
    rows = [line.split(",")[0] for line in
            capsys.readouterr().out.splitlines()]
    assert [r for r in rows if r.startswith("phases/rgg2d/")] == [
        "phases/rgg2d/boruvka_rounds", "phases/rgg2d/single_round",
        "phases/rgg2d/filter_sweep"]


def test_nothing_recorded_under_jit():
    u, v, w = _graph(3)
    e = from_numpy(u, v, w, N, pad_to=512)
    obs.clear()

    @jax.jit
    def solve(u, v, w):
        return minimum_spanning_forest(EdgeList(u, v, w, N))[0]

    solve(e.u, e.v, e.w)
    jax.jit(lambda u, v, w: minimum_spanning_forest(
        EdgeList(u, v, w, N), algorithm="filter_boruvka")[0]).lower(
        e.u, e.v, e.w)
    assert obs.solve_records() == []
    minimum_spanning_forest(e)
    assert len(obs.solve_records()) == 1


def test_records_ring_and_totals():
    obs.clear()
    with obs.span("x"):
        pass
    obs.record(rounds=[jnp.int32(2), 3], live_slots=jnp.arange(4),
               slot_rounds=[np.int32(7), [1, 2]])
    obs.record(rounds=1, live_slots=0, slot_rounds=0)
    first, second = obs.solve_records()
    assert (first["rounds"], first["live_slots"], first["slot_rounds"]) \
        == (5, 6, 10)
    assert set(first["host_s"]) == {"x"} and second["host_s"] == {}
    assert obs.solve_records(last=1) == [second]
    assert obs.solve_records(last=0) == []
    for _ in range(obs.RING + 5):
        obs.record(rounds=0)
    assert len(obs.solve_records()) == obs.RING
    with pytest.raises(ValueError):
        obs.scope("not_a_phase")


def _phases(text):
    """The phase scopes in a compiled program's op_name metadata."""
    names = re.findall(r'op_name="([^"]*)"', text)
    return {p for name in names for p in obs.PHASES
            if f"/{p}/" in f"/{name}/"}


def _sharded_programs(monkeypatch, **kw):
    """Compiled text of each program a p=1 sharded solve runs, by the
    name of the function that built it."""
    seen = {}
    for name, orig in PROGRAM_FNS.items():
        def build(*a, _orig=orig, _name=name, **k):
            fn = _orig(*a, **k)

            def call(*args):
                seen.setdefault(_name, (fn, args))
                return fn(*args)
            return call
        monkeypatch.setattr(ds, name, build)
    u, v, w = _graph(4)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    g, _ = build_dist_graph(u, v, w, N, 1)
    ds.distributed_sharded_msf(g, N, mesh, **kw)
    return {name: fn.lower(*args).compile().as_text()
            for name, (fn, args) in seen.items()}


STEP = {"label_gather", "minedges", "contract", "doubling", "sort",
        "exchange"}


def test_scopes_in_static_programs():
    u, v, w = _graph(5)
    e = from_numpy(u, v, w, N, pad_to=512)
    static = boruvka_msf_counted.lower(e.u, e.v, e.w, N).compile()
    assert _phases(static.as_text()) == {"label_gather", "minedges",
                                         "contract", "doubling"}
    filt = filter_boruvka_msf_counted.lower(e.u, e.v, e.w, N).compile()
    assert _phases(filt.as_text()) == {"label_gather", "minedges",
                                       "contract", "doubling", "sort"}


def test_scopes_in_sharded_programs(monkeypatch):
    progs = _sharded_programs(monkeypatch)
    assert _phases(progs["_build_sharded_prep_fn"]) == {
        "sort", "label_gather", "minedges", "contract", "doubling",
        "exchange"}
    assert _phases(progs["_build_ghost_setup_fn"]) == {
        "ghost_setup", "sort", "exchange"}
    ghost = _sharded_programs(monkeypatch, local_preprocessing=False)
    assert _phases(ghost["_build_sharded_round_fn"]) == STEP | {"push"}
    routed = _sharded_programs(monkeypatch, local_preprocessing=False,
                               ghost_cache=False)
    # without the ghost tables the endpoint labels come from lookups
    assert _phases(routed["_build_sharded_round_fn"]) == \
        STEP - {"label_gather"} | {"lookup"}


def test_driver_spans_in_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    u, v, w = _graph(6)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    g, _ = build_dist_graph(u, v, w, N, 1)
    kw = dict(local_preprocessing=False)
    ds.distributed_sharded_msf(g, N, mesh, **kw)  # compile outside
    trace = []
    jax.profiler.start_trace(str(tmp_path))
    ds.distributed_sharded_msf(g, N, mesh, round_trace=trace, **kw)
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    steps, names = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(obs.PREFIX):
                    names.add(e.name)
                if e.name == "msf.driver.step":
                    steps.append(dict(e.stats))
    assert {"msf.driver.lookup_bound", "msf.driver.readback",
            "msf.driver.index", "msf.driver.ghost_bounds",
            "msf.driver.ghost_setup", "msf.driver.bounds",
            "msf.driver.step", "msf.driver.finish"} <= names
    # the step span's args are the round_trace entry of its round (a
    # bool arg reads back as 0 or 1)
    assert len(steps) == len(trace) > 1
    for args, rec in zip(steps, trace):
        assert {k: str(int(v) if isinstance(v, bool) else v)
                for k, v in rec.items()} == {k: str(args[k]) for k in rec}
