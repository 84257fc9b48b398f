"""End-to-end LM training driver at smoke scale: any assigned arch, synthetic
tokens, AdamW, checkpoint/restart, loss must decrease.

    PYTHONPATH=src python examples/train_lm.py --arch mamba2-130m --steps 60

(Full-size configs are exercised by the 512-device dry-run:
 python -m repro.launch.dryrun --all.)
"""
import argparse
import os

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import build_model
from repro.data.tokens import synthetic_token_batch
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    cfg = get_config(args.arch).smoke()
    model = build_model(cfg, tp=1)
    step_fn, _ = model.make_train_step()
    step_fn = jax.jit(step_fn, donate_argnums=(0,))

    def batch_fn(step):
        b = synthetic_token_batch(step, args.batch, args.seq, cfg.vocab_size)
        if cfg.n_codebooks > 1:
            import numpy as np
            t = np.repeat(b["tokens"][:, None], cfg.n_codebooks, 1)
            l = np.repeat(b["labels"][:, None], cfg.n_codebooks, 1)
            b = {"tokens": t, "labels": l}
        return {k: jnp.asarray(v) for k, v in b.items()}

    losses = []

    def wrapped_step(state, batch):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if len(losses) % 10 == 0:
            print(f"step {len(losses):4d}  loss {losses[-1]:.4f}")
        return state, metrics

    loop = TrainLoop(
        TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                        ckpt_every=20,
                        metrics_path=os.path.join(args.ckpt_dir, "metrics.jsonl")),
        wrapped_step, batch_fn,
        lambda: model.init_train_state(jax.random.PRNGKey(0)))
    loop.run()

    first, last = sum(losses[:5]) / 5, sum(losses[-5:]) / 5
    print(f"\nloss {first:.4f} → {last:.4f} "
          f"({'OK: decreased' if last < first else 'WARNING: no decrease'})")
    print(f"checkpoints in {args.ckpt_dir}; rerun resumes from the latest.")


if __name__ == "__main__":
    enable_compile_cache()
    main()
