"""End-to-end training driver: train a ~100M-param llama-style model for a
few hundred steps on CPU with the full production substrate — sharded data
pipeline, AdamW (fp32 master), remat, async checkpointing with resume, and
the elastic mesh.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.compile_cache import enable_compile_cache
from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, Prefetcher, TokenSource
from repro.models.zoo import build_model
from repro.train import optimizer as optim
from repro.train.step import TrainState, init_train_state, make_train_step


def small_100m(tiny: bool = False):
    """~100M-param member of the llama3.2 family (tiny: ~23M CI variant)."""
    cfg = get_config("llama3.2-1b")
    if tiny:
        return dataclasses.replace(
            cfg, n_layers=6, d_model=512, n_heads=8, n_kv_heads=4,
            head_dim=64, d_ff=1536, vocab=8192, dtype=jnp.float32)
    return dataclasses.replace(
        cfg, n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
        head_dim=64, d_ff=2048, vocab=16384, dtype=jnp.float32)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--tiny", action="store_true",
                    help="~23M CI variant (default is ~100M)")
    args = ap.parse_args()

    cfg = small_100m(tiny=args.tiny)
    model = build_model(cfg)
    n_params = None

    ckpt = Checkpointer(args.ckpt_dir, keep=2)
    state = init_train_state(model, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"model: {cfg.name} variant, {n_params/1e6:.1f}M params")

    start = 0
    latest = ckpt.latest_step()
    if latest is not None:
        print(f"resuming from checkpoint step {latest}")
        state = ckpt.restore(latest, state)
        start = latest

    opt_cfg = optim.OptConfig(lr=3e-4, warmup_steps=20,
                              total_steps=args.steps)
    step_fn = jax.jit(make_train_step(model, opt_cfg))

    data = TokenSource(DataConfig(seq_len=args.seq,
                                  global_batch=args.batch, vocab=cfg.vocab))
    prefetch = Prefetcher(data, start_step=start)

    t0 = time.time()
    try:
        for i in range(start, args.steps):
            _, batch = next(prefetch)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = step_fn(state, batch)
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:4d}  loss={float(metrics['loss']):.4f}  "
                      f"gnorm={float(metrics['grad_norm']):.3f}  "
                      f"lr={float(metrics['lr']):.2e}  "
                      f"({(time.time()-t0):.0f}s)", flush=True)
            if i and i % args.ckpt_every == 0:
                ckpt.save(i, state)      # async, off the critical path
    finally:
        prefetch.close()
        ckpt.wait()
    ckpt.save(args.steps, state, blocking=True)
    print(f"done: {args.steps} steps in {time.time()-t0:.0f}s; "
          f"final loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
