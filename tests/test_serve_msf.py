"""MSF serving gateway (ISSUE 6): plan-cache keying, family-calibrated
synthetic plans (in-process), and the gateway's serving contract on 8
virtual devices (subprocess) — oracle bit-identity of every served
forest, hit/miss/evict accounting, the replan fallback for traffic
whose shapes match a cached plan but whose structure overflows it, and
the drift-triggered plan refresh."""
import math

import numpy as np
import pytest

from repro.core.distributed import quantize_capacity, shrink_schedule
from repro.core.plan import plan_cache_key, synthetic_plan
from tests.helpers.subproc import run_multidevice


# -- cache keying (in-process) ---------------------------------------------

def test_plan_cache_key_stable_and_discriminating():
    sp = synthetic_plan(256, 8 * 64, 8)
    # the key a gateway computes BEFORE measuring equals the key the
    # measured plan reports — one cache slot per (family, shape, levers)
    # (synthetic plans freeze relabel_skip=False: they cannot model the
    # settled-vertex capacity drop, so the key must say so)
    pre = plan_cache_key("gnm", 256, 8, 64, "boruvka", relabel_skip=False)
    assert sp.cache_key("gnm") == pre
    # pad() buys capacity headroom without changing cache identity
    assert sp.pad(0.5).cache_key("gnm") == pre
    # family / shape / algorithm / levers all discriminate
    kw = dict(relabel_skip=False)
    assert plan_cache_key("rgg2d", 256, 8, 64, **kw) != pre
    assert plan_cache_key("gnm", 512, 8, 64, **kw) != pre
    assert plan_cache_key("gnm", 256, 8, 128, **kw) != pre
    assert plan_cache_key("gnm", 256, 8, 64, "filter_boruvka", **kw) != pre
    assert plan_cache_key("gnm", 256, 8, 64, coalesce=False, **kw) != pre
    assert plan_cache_key("gnm", 256, 8, 64) != pre   # relabel_skip itself


# -- family-calibrated synthetic plans (in-process) ------------------------

def test_synthetic_plan_family_models():
    n, p, cap = 4096, 8, 4096
    vps = 512
    ladder = shrink_schedule(cap)
    # gnm: the MINEDGES exchange is bounded by one candidate per source
    # vertex, so cap_edge plateaus at the vertices-per-shard rung
    sp = synthetic_plan(n, p * cap, p, family="gnm")
    plateau = quantize_capacity(vps, cap)
    assert all(r.cap_edge == plateau for r in sp.rounds)
    # rgg2d: halves from the cap/p rung
    sp = synthetic_plan(n, p * cap, p, family="rgg2d")
    caps = [r.cap_edge for r in sp.rounds]
    start = ladder.index(quantize_capacity(-(-cap // p), cap))
    for r, c in enumerate(caps):
        assert c == ladder[min(start + r, len(ladder) - 1)], (r, c)
    # family=None keeps the generic full-cap halving (backward compat)
    sp = synthetic_plan(n, p * cap, p)
    assert [r.cap_edge for r in sp.rounds][:3] == [4096, 2048, 1024]
    with pytest.raises(ValueError, match="family"):
        synthetic_plan(n, p * cap, p, family="rhg")
    # calibrated plans stay structurally valid + durable
    synthetic_plan(n, p * cap, p, family="gnm").validate()


def test_build_dist_graph_cap_pad():
    from repro.core.distributed import build_dist_graph
    rng = np.random.default_rng(0)
    u = rng.integers(0, 64, 100).astype(np.int32)
    v = (u + 1 + rng.integers(0, 62, 100).astype(np.int32)) % 64
    w = rng.uniform(1, 10, 100).astype(np.float32)
    g0, need = build_dist_graph(u, v, w, 64, 8)
    g1, cap = build_dist_graph(u, v, w, 64, 8, cap=64)
    assert need == 25 and cap == 64
    assert g1.u.shape == (8 * 64,)
    # padding slots are INVALID_W; every real edge copy is preserved
    assert int(np.isfinite(np.asarray(g1.w)).sum()) == 200
    assert np.isclose(np.asarray(g1.w)[np.isfinite(np.asarray(g1.w))].sum(),
                      2 * w.sum())
    with pytest.raises(ValueError, match="cap"):
        build_dist_graph(u, v, w, 64, 8, cap=8)


# -- the serving gateway (subprocess, 8 virtual devices) -------------------

GATEWAY = """
from jax.sharding import Mesh
from repro.core import oracle
from repro.launch.serve_msf import make_traffic
from repro.serve.msf_gateway import MSFGateway, MSFRequest

p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))

def check(reqs):
    for r in reqs:
        kmask, kweight = oracle.kruskal(r.u, r.v, r.w, r.n)
        assert np.array_equal(r.edges, np.nonzero(kmask)[0]), (
            r.rid, "served forest != oracle")
        assert abs(r.weight - kweight) < 1e-3 * max(1.0, kweight), r.rid

# (1) hit / miss / evict accounting + oracle bit-identity.  16 requests
# cycling gnm/rgg2d at n=256 -> 2 cache keys, 4 batches of 4.
gw = MSFGateway(mesh, cache_size=2, batch_slots=4)
reqs = make_traffic(("gnm", "rgg2d"), (256,), 16, seed=0)
for r in reqs:
    gw.submit(r)
gw.run()
assert all(r.done for r in reqs)
check(reqs)
s = gw.stats
assert s.served == 16 and s.batches == 4, vars(s)
assert (s.hits, s.misses, s.evictions) == (2, 2, 0), vars(s)
assert len(gw.cache) == 2

# a third key at capacity 2 evicts the least-recently-used entry ...
extra = make_traffic(("gnm",), (384,), 2, seed=50)
for r in extra:
    gw.submit(r)
gw.run()
check(extra)
assert s.misses == 3 and s.evictions == 1 and len(gw.cache) == 2, vars(s)
# ... which was the gnm/256 key (rgg2d/256 was served later), so
# gnm/256 traffic misses again — and evicts the next LRU entry
again = make_traffic(("gnm",), (256,), 2, seed=60)
for r in again:
    gw.submit(r)
gw.run()
check(again)
assert s.misses == 4 and s.hits == 2 and s.evictions == 2, vars(s)

# (2) replan fallback under serving (satellite): traffic whose SHAPE
# matches a cached plan but whose STRUCTURE overflows it.  A star
# graph (hub + n-1 leaves) converges in one Boruvka round, so its
# measured plan has far too few rounds for a path graph of the same
# n and edge count (needs ~log2 n rounds) — same family label, same
# n, same m -> same cache key, guaranteed structural misfit.
n2 = 256
def star(seed):
    rng = np.random.default_rng(seed)
    u = np.zeros(n2 - 1, np.int32)
    v = np.arange(1, n2, dtype=np.int32)
    return u, v, rng.uniform(1, 10, n2 - 1).astype(np.float32)

def path(seed):
    rng = np.random.default_rng(seed)
    u = np.arange(0, n2 - 1, dtype=np.int32)
    v = np.arange(1, n2, dtype=np.int32)
    return u, v, rng.uniform(1, 10, n2 - 1).astype(np.float32)

gw2 = MSFGateway(mesh, cache_size=4, batch_slots=4,
                 replan_threshold=0.34, min_samples=4)
rid = 0
stars = []
for seed in range(4):
    u, v, w = star(seed)
    stars.append(MSFRequest(rid=rid, family="syn", u=u, v=v, w=w, n=n2))
    rid += 1
for r in stars:
    gw2.submit(r)
gw2.run()   # one miss; plan measured on a star graph
check(stars)
assert gw2.stats.misses == 1 and gw2.stats.replans == 0, vars(gw2.stats)
key = gw2._key(stars[0])

# same-key path traffic: every request must replan individually (the
# batchmate isolation is per-request overflow/residual), results stay
# oracle-exact, the replan counter moves, the cache entry survives
paths = []
for seed in range(4):
    u, v, w = path(100 + seed)
    paths.append(MSFRequest(rid=rid, family="syn", u=u, v=v, w=w, n=n2))
    rid += 1
for r in paths:
    gw2.submit(r)
gw2.run()
check(paths)
assert all(r.served_via == "replanned" for r in paths)
assert gw2.stats.hits == 1 and gw2.stats.replans == 4, vars(gw2.stats)
# drift: replan rate 4/8 crossed the threshold -> the entry was
# re-measured off a replanned (path) graph and refreshed in place
assert gw2.stats.refreshes == 1, vars(gw2.stats)
assert key in gw2.cache and len(gw2.cache) == 1
entry = gw2.cache[key]
assert (entry.served, entry.replans) == (0, 0)   # fresh counters

# post-refresh, identical-weights path traffic rides the refreshed
# plan batched — no replans (same trajectory the refresh measured)
paths2 = []
for i in range(4):
    u, v, w = path(103)   # == the graph the refresh measured on
    paths2.append(MSFRequest(rid=rid, family="syn", u=u, v=v, w=w, n=n2))
    rid += 1
for r in paths2:
    gw2.submit(r)
gw2.run()
check(paths2)
assert all(r.served_via == "batched" for r in paths2), \
    [r.served_via for r in paths2]
assert gw2.stats.replans == 4 and gw2.stats.refreshes == 1, vars(gw2.stats)
print("OK")
"""


def test_gateway_multidevice():
    out = run_multidevice(GATEWAY, ndev=8, timeout=1800)
    assert "OK" in out


# -- batchmate failure attribution + rung deadlines (subprocess) -----------

BATCH_ATTRIBUTION = """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import (execute_plan_batched,
                                            plan_sharded_msf)

p = 8
n = 256
mesh = Mesh(np.array(jax.devices()), ("data",))
rng = np.random.default_rng(0)

# two same-shape batchmates, one good, one "corrupt" for the measured
# plan: a star converges in one round, a path of the same n and m
# needs ~log2 n — the plan strictly fits only the star lane
su = np.zeros(n - 1, np.int32)
sv = np.arange(1, n, dtype=np.int32)
pu = np.arange(0, n - 1, dtype=np.int32)
pv = np.arange(1, n, dtype=np.int32)
w1 = rng.uniform(1, 10, n - 1).astype(np.float32)
w2 = rng.uniform(1, 10, n - 1).astype(np.float32)
cap = max(1, -(-2 * (n - 1) // p))
star = build_dist_graph(su, sv, w1, n, p, cap=cap)[0]
path = build_dist_graph(pu, pv, w2, n, p, cap=cap)[0]
km_s, kw_s = oracle.kruskal(su, sv, w1, n)
km_p, kw_p = oracle.kruskal(pu, pv, w2, n)
plan = plan_sharded_msf(star, n, mesh)

def eids(g, res):
    return np.unique(np.asarray(g.eid)[np.asarray(res[0])])

# defer mode: ONLY the corrupt lane is flagged (None result); the good
# batchmate's forest is untouched — oracle-bit-identical
res, flagged = execute_plan_batched([star, path], n, mesh, plan,
                                    replan="defer", verify=True)
assert flagged == (1,), flagged
assert res[1] is None
assert np.array_equal(eids(star, res[0]), np.flatnonzero(km_s))
assert abs(float(res[0][1]) - kw_s) < 1e-3 * kw_s
assert int(res[0][4]) == 0

# lane order is attribution, not position: swap the batch
res2, flagged2 = execute_plan_batched([path, star], n, mesh, plan,
                                      replan="defer", verify=True)
assert flagged2 == (0,), flagged2
assert res2[0] is None
assert np.array_equal(eids(star, res2[1]), np.flatnonzero(km_s))

# strict mode raises naming exactly the corrupted index
try:
    execute_plan_batched([star, path], n, mesh, plan, replan=False,
                         verify=True)
    raise SystemExit("misfit lane was silent under replan=False")
except RuntimeError as e:
    assert "batch requests [1]" in str(e), e

# serving mode: the corrupt lane is still attributed in ``flagged``
# but comes back re-solved by its own measured pass — both lanes end
# oracle-exact, the good lane from the shared batched dispatch
res3, flagged3 = execute_plan_batched([star, path], n, mesh, plan,
                                      replan=True, verify=True)
assert flagged3 == (1,), flagged3
assert np.array_equal(eids(star, res3[0]), np.flatnonzero(km_s))
assert np.array_equal(eids(path, res3[1]), np.flatnonzero(km_p))
assert abs(float(res3[1][1]) - kw_p) < 1e-3 * kw_p
assert int(res3[1][4]) == 0
print("OK")
"""


@pytest.mark.slow
def test_batchmate_failure_attribution_multidevice():
    assert run_multidevice(BATCH_ATTRIBUTION, ndev=8,
                           timeout=900).strip().endswith("OK")


RUNG_DEADLINE = """
from jax.sharding import Mesh
from repro.core import oracle
from repro.serve.msf_gateway import MSFGateway, MSFRequest

p = 8
n = 256
mesh = Mesh(np.array(jax.devices()), ("data",))

def star(seed, rid, deadline=None):
    rng = np.random.default_rng(seed)
    return MSFRequest(rid=rid, family="syn", u=np.zeros(n - 1, np.int32),
                      v=np.arange(1, n, dtype=np.int32),
                      w=rng.uniform(1, 10, n - 1).astype(np.float32),
                      n=n, deadline=deadline)

def path(seed, rid, deadline=None):
    rng = np.random.default_rng(seed)
    return MSFRequest(rid=rid, family="syn",
                      u=np.arange(0, n - 1, dtype=np.int32),
                      v=np.arange(1, n, dtype=np.int32),
                      w=rng.uniform(1, 10, n - 1).astype(np.float32),
                      n=n, deadline=deadline)

# regression (ISSUE 9 bugfix): the entry sweep runs before the batched
# dispatch, so a request that was inside its deadline at step entry
# can be expired by the time its retry rung dispatches.  Cold gateway:
# the star heads the batch, the plan is measured on it (seconds of
# compile on this backend — far past the path's 1s budget), the path
# lane flags, and the rung's re-check must reject instead of serving
# late.  Pre-fix, the rung dispatched a strict replan and served a
# result past the deadline.
gw = MSFGateway(mesh, batch_slots=4, max_retries_per_request=3,
                breaker_threshold=99, min_samples=99)
s0 = star(0, 0)
p0 = path(1, 1, deadline=1.0)
gw.submit(s0)
gw.submit(p0)
gw.run()
assert s0.done and s0.served_via == "batched"
km, kw = oracle.kruskal(s0.u, s0.v, s0.w, n)
assert np.array_equal(s0.edges, np.flatnonzero(km))
assert p0.done and p0.served_via == "rejected", vars(p0)
assert "before retry dispatch" in p0.error, p0.error
assert gw.stats.deadline_missed == 1 and gw.stats.rejected == 1
assert gw.stats.retried == 1 and not gw.queue
# the rung rejection never consumed a replan or resumed a checkpoint
assert gw.stats.replans == 0 and gw.stats.resumed == 0

# same traffic with budget to spare serves via the ladder as before —
# the re-check only fires for genuinely expired requests
p1 = path(2, 2, deadline=600.0)
gw.submit(p1)
gw.run()
assert p1.done and p1.served_via == "replanned", vars(p1)
km, kw = oracle.kruskal(p1.u, p1.v, p1.w, n)
assert np.array_equal(p1.edges, np.flatnonzero(km))
assert gw.stats.deadline_missed == 1, vars(gw.stats)
print("OK")
"""


@pytest.mark.slow
def test_rung_deadline_recheck_multidevice():
    assert run_multidevice(RUNG_DEADLINE, ndev=8,
                           timeout=900).strip().endswith("OK")


# -- synthetic-plan calibration vs measured plans (subprocess) -------------

CALIBRATION = """
from jax.sharding import Mesh
from repro.core.distributed import build_dist_graph, shrink_schedule
from repro.core.distributed_sharded import plan_sharded_msf
from repro.core.plan import synthetic_plan

from repro.data import generators

p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))
for fam in ("gnm", "rgg2d"):
    u, v, w, n = generators.generate(fam, 4096, avg_degree=8.0, seed=3)
    g, cap = build_dist_graph(u, v, w, n, p)
    measured = plan_sharded_msf(g, n, mesh, axis_names=("data",))
    synth = synthetic_plan(n, g.cap_total, p, family=fam)
    assert synth.cap_per_shard == measured.cap_per_shard
    ladder = shrink_schedule(cap)
    m_caps = [r.cap_edge for r in measured.rounds if not r.sentinel]
    s_caps = [r.cap_edge for r in synth.rounds if not r.sentinel]
    # the calibrated trajectory tracks the measured plan within one
    # ladder rung, round for round (ISSUE 6 acceptance; the generic
    # halving ladder misses the gnm plateau by 3+ rungs mid-solve)
    for r, (mc, sc) in enumerate(zip(m_caps, s_caps)):
        mi, si = ladder.index(mc), ladder.index(sc)
        assert abs(mi - si) <= 1, (fam, r, mc, sc, m_caps, s_caps)
    print(fam, "measured", m_caps, "synthetic", s_caps[:len(m_caps)])
print("OK")
"""


def test_synthetic_plan_calibration_multidevice():
    out = run_multidevice(CALIBRATION, ndev=8, timeout=1800)
    assert "OK" in out

