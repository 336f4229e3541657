"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU host it runs reduced (smoke) configs end-to-end — the same
code path the production mesh uses, minus scale: sharded params via the
same rules, fault-tolerant checkpointing, straggler monitor, prefetching
loader. See examples/train_lm.py for the ~100M-param end-to-end run.
"""

from __future__ import annotations

import argparse
import json
import os

import jax

from ..configs import get_config
from ..data.synthetic import DataConfig
from ..dist.sharding import TRAIN_RULES, sharding_rules
from ..models import init_lm
from ..models.encdec import init_encdec
from ..optim import AdamWConfig
from ..train import Trainer, TrainerConfig
from .compile_cache import enable_compilation_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    enable_compilation_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(0)
    init = init_encdec if cfg.is_encoder_decoder else init_lm
    params = init(key, cfg)
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch
    )
    ckpt_dir = os.path.join(args.ckpt_dir, args.arch.replace("/", "_"))
    trainer = Trainer(
        cfg, params, data_cfg, ckpt_dir,
        opt_cfg=AdamWConfig(lr=args.lr),
        trainer_cfg=TrainerConfig(
            total_steps=args.steps, ckpt_every=args.ckpt_every,
            n_microbatches=args.microbatches,
        ),
    )
    log = trainer.run()
    print(json.dumps(log[-3:], indent=1))
    print(f"final loss: {log[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
