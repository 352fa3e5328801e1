"""Train a small LM for a few hundred steps with the full substrate
(AdamW, remat'd scanned layers, checkpointing + auto-resume).

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import place_compile_cache
from repro.configs.base import get_arch
from repro.train.optimizer import AdamWConfig
from repro.train.train_loop import TrainConfig, train


def synthetic_data(cfg, batch=16, seq=64, seed=0):
    """Deterministic affine-next-token stream: learnable in minutes."""
    rng = np.random.default_rng(seed)
    V = cfg.vocab_size
    while True:
        t0 = rng.integers(0, V, (batch, 1))
        seq_arr = [t0]
        for _ in range(seq):
            seq_arr.append((seq_arr[-1] * 5 + 7) % V)
        arr = np.concatenate(seq_arr, axis=1)
        yield {"tokens": jnp.asarray(arr[:, :seq], jnp.int32),
               "labels": jnp.asarray(arr[:, 1:seq + 1], jnp.int32)}


def main() -> None:
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    args = ap.parse_args()

    # reduced config, scaled up a little beyond the smoke size
    cfg = get_arch(args.arch).smoke
    cfg = dataclasses.replace(cfg, num_layers=4, d_model=128, d_ff=384,
                              num_heads=8, num_kv_heads=4)
    tc = TrainConfig(
        opt=AdamWConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps),
        ckpt_dir=args.ckpt, ckpt_every=50, log_every=10)
    res = train(cfg, tc, synthetic_data(cfg), num_steps=args.steps)
    print(f"final loss: {res['losses'][-1]:.4f} "
          f"(from {res['losses'][0]:.4f})")


if __name__ == "__main__":
    main()
