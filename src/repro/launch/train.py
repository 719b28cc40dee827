"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it runs reduced (smoke) configs end-to-end through
the full production path — executor-prefetched data, jitted train step,
async checkpoints, restart.  On a TPU cluster the same entrypoint binds
the production mesh and sharding rules (``--production``).
"""
from __future__ import annotations

import argparse

import jax

from ..configs import get_config, get_smoke_config
from ..optim import OptHParams
from ..sharding.logical import use_rules
from ..train import TrainConfig
from ..train.trainer import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache
from .mesh import make_production_mesh, make_rules


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--grad-sync", default="auto", choices=["auto", "int8_ef"])
    ap.add_argument("--grad-pack", default="host", choices=["host", "device"],
                    help="explicit-DP wire packer: host reference loop or the "
                         "fused device kernel (bit-identical wire bytes)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production", action="store_true", help="bind the 16x16 production mesh")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    arch = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    hp = OptHParams(lr_peak=args.lr, warmup_steps=max(args.steps // 10, 1), total_steps=args.steps)
    tcfg = TrainConfig(microbatches=args.microbatches, remat=args.remat,
                       grad_sync=args.grad_sync, grad_pack=args.grad_pack)
    run = TrainerConfig(
        batch=args.batch,
        seq=args.seq,
        steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
    )

    def go():
        trainer = Trainer(arch, hp, tcfg, run)
        summary = trainer.train()
        print("summary:", summary)
        return 0

    if args.production:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        with use_rules(make_rules(mesh)), mesh:
            return go()
    return go()


if __name__ == "__main__":
    raise SystemExit(main())
