"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On real hardware this binds the production mesh and the dry-run-validated
shardings; on the CPU host it runs a reduced config end-to-end (the same
Trainer, SmartConf controllers, checkpointing, fault-tolerance paths).
"""

from __future__ import annotations

import argparse

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import reduced
from repro.launch.runtime import enable_compile_cache
from repro.optim import adamw
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workdir", default="experiments/train")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full architecture (TPU-scale memory!)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = reduced(cfg)
    print(f"training {cfg.name}: ~{cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps @ batch {args.batch}x{args.seq}")
    tc = TrainerConfig(workdir=args.workdir, total_steps=args.steps,
                       ckpt_interval=max(args.steps // 5, 1),
                       batch_size=args.batch, seq_len=args.seq,
                       n_micro=args.microbatches)
    opt = adamw.AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                            total_steps=args.steps)
    tr = Trainer(cfg, opt, tc)
    tr.preemption.install()
    log = tr.run()
    if log:
        print(f"loss {log[0]['loss']:.4f} -> {log[-1]['loss']:.4f}; "
              f"last ckpt @ step {tr.ckpt.last_saved}")
    tr.close()


if __name__ == "__main__":
    main()
