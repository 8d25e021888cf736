"""End-to-end LM training driver: data pipeline -> sharded train step ->
async checkpoints -> fault-tolerant loop, on any of the ten assigned
architectures (reduced or full preset).

    PYTHONPATH=src python examples/train_lm.py --arch qwen3-32b --steps 300
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

Presets:
    reduced  the arch's CPU smoke config (default; runs anywhere)
    100m     a ~100M-param qwen3-family config (the deliverable-scale run;
             a few hundred steps is hours on 1 CPU core, minutes on a TPU
             host — start it with --steps 300 where you have silicon)

The loop itself is the production Trainer: resumable (re-run the same
command after killing it and it continues from the last checkpoint),
failure-injectable (--inject-failure N kills step N once), straggler-
tracked.
"""

import argparse
import dataclasses
import sys

sys.path.insert(0, "src")

import jax

from repro import optim
from repro.compat import enable_compile_cache, make_mesh
from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.train import Trainer, TrainerConfig


def preset_100m(base):
    """~100M-param qwen3-family config (exact count printed at start)."""
    return dataclasses.replace(
        base,
        n_layers=12,
        d_model=768,
        n_heads=12,
        n_kv=4,
        head_dim=64,
        d_ff=2048,
        vocab=32768,
        compute_dtype=jax.numpy.float32,
        remat="none",
        scan_layers=True,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--preset", default="reduced", choices=["reduced", "100m"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--inject-failure", type=int, default=-1)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    cfg = preset_100m(cfg) if args.preset == "100m" else cfg.reduced()
    from repro.models.model import param_counts

    n = param_counts(cfg)["total"]
    print(f"arch={cfg.name} preset={args.preset}: {n/1e6:.1f}M params")

    shape = ShapeConfig("train", seq_len=args.seq, global_batch=args.batch,
                        kind="train")
    mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
    trainer = Trainer(
        cfg, shape, mesh,
        TrainerConfig(steps=args.steps, ckpt_every=max(args.steps // 4, 10),
                      ckpt_dir=args.ckpt_dir, log_every=10),
        opt_cfg=optim.AdamWConfig(
            lr=optim.warmup_cosine(args.lr, warmup=20, total=args.steps)
        ),
    )
    fail = {args.inject_failure} if args.inject_failure >= 0 else set()

    def inject(step):
        if step in fail:
            fail.discard(step)
            return True
        return False

    out = trainer.train(inject_failure=inject)
    first = out["metrics"][0]["loss"] if out["metrics"] else float("nan")
    last = out["metrics"][-1]["loss"] if out["metrics"] else float("nan")
    print(
        f"done: {out['step']} steps, loss {first:.3f} -> {last:.3f}, "
        f"stragglers={out['stragglers']} failures={out['failures']}"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
