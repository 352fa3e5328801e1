"""Paper Fig. 4 analog: local preprocessing on/off on high-locality graphs.

Derived metrics: fraction of MSF edges contracted communication-free and
the number of distributed rounds that remain — the structural source of
the paper's up-to-5x speedup.  8 virtual devices in a subprocess.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json, time
from jax.sharding import Mesh
from repro.core.distributed import build_dist_graph, distributed_msf
from repro.data import generators

mesh = Mesh(np.array(jax.devices()), ("data",))
out = {}
for fam in ("grid2d", "rgg2d", "rhg", "gnm"):
    u, v, w, n = generators.generate(fam, 4096, avg_degree=8.0, seed=2)
    g, cap = build_dist_graph(u, v, w, n, 8)
    rec = {}
    for pre in (True, False):
        t0 = time.perf_counter()
        mask, wt, cnt, labels, stats = distributed_msf(
            g, n, mesh, algorithm="boruvka", axis_names=("data",),
            local_preprocessing=pre)
        jax.block_until_ready(mask)
        t1 = time.perf_counter()
        # time a second run (compiled)
        t0 = time.perf_counter()
        mask, wt, cnt, labels, stats = distributed_msf(
            g, n, mesh, algorithm="boruvka", axis_names=("data",),
            local_preprocessing=pre)
        jax.block_until_ready(mask)
        us = (time.perf_counter() - t0) * 1e6
        rec[str(pre)] = {"us": us, "mst_edges": int(cnt)}
    # contracted fraction: run preprocessing alone
    from repro.core.distributed import _local_preprocessing
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    def body(uu, vv, ww, ee):
        valid = jnp.isfinite(ww)
        labels, mst = _local_preprocessing(uu, vv, ww, ee, valid, n,
                                           ("data",))
        return jax.lax.psum(mst.sum(), ("data",))
    f = shard_map(body, mesh=mesh, in_specs=(P("data"),) * 4, out_specs=P())
    contracted = int(f(g.u, g.v, g.w, g.eid))
    rec["contracted_frac"] = contracted / max(rec["True"]["mst_edges"], 1)
    out[fam] = rec
print(json.dumps(out))
"""


def run() -> None:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for fam, rec in out.items():
        on, off = rec["True"]["us"], rec["False"]["us"]
        emit(f"preprocessing/{fam}/on", on,
             f"contracted_frac={rec['contracted_frac']:.3f}")
        emit(f"preprocessing/{fam}/off", off,
             f"speedup_from_preprocessing={off / max(on, 1):.2f}x")


if __name__ == "__main__":
    run()
