"""Serve a small model with batched continuous-batching decode.

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax

from repro.compile_cache import place_compile_cache
from repro.configs.base import get_arch
from repro.models.model import init_params
from repro.serve.engine import Request, ServeEngine


def main() -> None:
    place_compile_cache()
    cfg = get_arch("qwen2-1.5b").smoke
    params = init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, batch_slots=4, max_len=96,
                      temperature=0.0)
    prompts = [[1, 2, 3], [7, 8], [11, 12, 13, 14], [5], [9, 10], [2, 4]]
    reqs = [Request(rid=i, prompt=p, max_new=24)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    total = sum(len(r.out) for r in reqs)
    for r in reqs:
        print(f"req {r.rid}: prompt={r.prompt} -> {r.out[:10]}"
              f"{'...' if len(r.out) > 10 else ''}")
    print(f"{len(reqs)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, 4 slots, continuous batching)")


if __name__ == "__main__":
    main()
