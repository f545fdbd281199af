"""Quickstart: train a small LM end-to-end with the full substrate —
sharded data collection, AdamW, checkpointing, straggler mitigation.

CPU-sized by default (~1M params, 60 steps); pass --steps/--dim to grow.
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

import jax
from repro.configs import get_config
from repro.checkpoint import CheckpointManager
from repro.core import PlaceGroup
from repro.data import ShardedBatches, TokenSource
from repro.models import Parallel, zoo
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime import StragglerMitigator
from repro.train.step import build_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_quickstart_ckpt")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced(
        n_layers=args.layers, d_model=args.dim, d_ff=args.dim * 3,
        vocab_size=4096)
    par = Parallel(mesh=None)
    params = zoo.init_params(cfg, 0)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} (reduced) params={n_params/1e6:.2f}M")

    opt = AdamWConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps)
    step, _, _ = build_train_step(cfg, par, opt)
    opt_state = adamw_init(params, opt)

    # data rows live in a relocatable collection (4 simulated data shards)
    group = PlaceGroup(4)
    src = TokenSource(cfg.vocab_size, args.seq, seed=0)
    shards = ShardedBatches(group, args.batch, src)
    mitigator = StragglerMitigator(4, period=10)
    ckpt = CheckpointManager(args.ckpt, keep=2)

    t0 = time.time()
    for i in range(args.steps):
        parts = [shards.local_batch(p) for p in group.members]
        batch = {
            "tokens": np.concatenate([b["tokens"] for b in parts]),
            "labels": np.concatenate([b["labels"] for b in parts]),
        }
        step_t0 = time.time()
        params, opt_state, metrics = step(params, opt_state, batch)
        dt = time.time() - step_t0
        shards.advance()
        # fake per-shard timings (even cluster) → no relocation expected
        mitigator.observe_and_maybe_rebalance(
            np.full(4, dt / 4), shards)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
        if i and i % 25 == 0:
            ckpt.save(i, {"params": params, "opt": opt_state})
    print(f"done in {time.time()-t0:.1f}s; "
          f"moves={mitigator.moves_applied} (expected 0 on even cluster)")
    ckpt.save(args.steps, {"params": params, "opt": opt_state})
    restored, manifest = ckpt.restore({"params": params, "opt": opt_state})
    print(f"checkpoint restored from step {manifest['step']} OK")


if __name__ == "__main__":
    main()
