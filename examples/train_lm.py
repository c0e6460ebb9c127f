"""End-to-end LM training driver: train a reduced granite-3 family model for
a few hundred steps with checkpoint/restart, demonstrating the full training
substrate (data pipeline -> sharded AdamW -> checkpoints -> resume).

    PYTHONPATH=src python examples/train_lm.py [--steps 200]

On a TPU fleet the identical entry point trains the full assigned configs via
``python -m repro.launch.train --arch granite-3-2b``.
"""
import argparse
import dataclasses
import shutil

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              n_layers=4, d_model=128, d_ff=512, vocab=512)
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    def hook(step, m):
        if step % 20 == 0:
            print(f"step {step:4d}  loss {m['loss']:.4f}  "
                  f"{m['dt'] * 1e3:6.1f} ms/step", flush=True)

    half = args.steps // 2
    print(f"phase 1: {half} steps with checkpointing ...")
    train(cfg, steps=half, batch=args.batch, seq=args.seq, lr=1e-3,
          ckpt_dir=args.ckpt_dir, ckpt_every=25, hook=hook)
    print("simulated restart — resuming from the latest checkpoint ...")
    res = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                lr=1e-3, ckpt_dir=args.ckpt_dir, ckpt_every=25, hook=hook)
    print(f"resumed from step {res.resumed_from}; "
          f"final loss {res.losses[-1]:.4f} "
          f"(from {res.losses[0]:.4f} post-resume)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
