"""Paper Fig. 5 analog: strong scaling — fixed graph, growing shard count.

On one physical CPU the wall time of virtual-device runs measures
*overhead*, not network speedup, so the primary derived metrics are
structural: max edges per shard (load balance) and bottleneck collective
volume per device, which are what determine scaling on real hardware.
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

u, v, w, n = generators.generate("rmat", 8192, avg_degree=16.0, seed=3)
out = {}
for p in (1, 2, 4, 8):
    mesh = Mesh(np.array(jax.devices())[:p], ("data",))
    g, cap = build_dist_graph(u, v, w, n, p)
    mask, wt, cnt, _, _ = distributed_msf(g, n, mesh, algorithm="boruvka",
                                          axis_names=("data",))
    jax.block_until_ready(mask)
    t0 = time.perf_counter()
    mask, wt, cnt, _, _ = distributed_msf(g, n, mesh, algorithm="boruvka",
                                          axis_names=("data",))
    jax.block_until_ready(mask)
    us = (time.perf_counter() - t0) * 1e6
    out[p] = {"us": us, "cap_per_shard": cap, "mst_edges": int(cnt)}
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
    base_cap = out["1"]["cap_per_shard"]
    for p, rec in out.items():
        emit(f"strong_scaling/rmat/p={p}", rec["us"],
             f"edges_per_shard={rec['cap_per_shard']};"
             f"parallel_efficiency_structural="
             f"{base_cap / (int(p) * rec['cap_per_shard']):.2f}")


if __name__ == "__main__":
    run()
