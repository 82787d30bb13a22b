"""End-to-end training driver: ``python -m repro.launch.train --arch <id>``.

Runs a real (CPU-sized by default) training loop with the paper's checkpoint
engine in the loop: periodic async checkpoints, kill-resume fault tolerance,
engine/strategy selection, and a final report of checkpoint overheads —
the framework-level analogue of the paper's Fig 3 experiment.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core import EngineConfig
from repro.data import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.optim import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def build_trainer(args) -> Trainer:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.scaled_down(layers=args.layers, width_div=args.width_div,
                              vocab=args.vocab)
    tcfg = TrainerConfig(
        steps=args.steps, ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
        ckpt_engine=args.engine, async_ckpt=not args.sync_ckpt,
        multilevel_remote=args.remote_dir, keep=args.keep,
        log_every=args.log_every, seed=args.seed)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.batch, seed=args.seed,
                          frontend_len=cfg.frontend_len,
                          frontend_dim=cfg.frontend_dim)
    eng_cfg = EngineConfig(strategy=args.strategy, direct=not args.buffered,
                           queue_depth=args.queue_depth)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh(d, m)
    return Trainer(cfg, tcfg, mesh=mesh, data_cfg=data_cfg,
                   opt_cfg=AdamWConfig(lr=args.lr),
                   engine_config=eng_cfg)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="xlstm-350m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="scaled-down config (full config needs a real pod)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--width-div", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--mesh", default="", help="e.g. 2x4 (data x model)")
    # checkpointing
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--remote-dir", default="")
    ap.add_argument("--keep", type=int, default=3,
                    help="newest committed steps to retain")
    ap.add_argument("--engine", default="aggregated",
                    choices=["aggregated", "datastates", "snapshot",
                             "torchsave"])
    ap.add_argument("--strategy", default="single_file",
                    choices=["single_file", "file_per_process",
                             "file_per_tensor"])
    ap.add_argument("--sync-ckpt", action="store_true")
    ap.add_argument("--buffered", action="store_true")
    ap.add_argument("--queue-depth", type=int, default=64)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--json-out", default="")
    return ap.parse_args(argv)


def main() -> None:
    args = parse_args()
    use_compile_cache()
    trainer = build_trainer(args)
    try:
        out = trainer.run()
    finally:
        trainer.close()
    losses = [m["loss"] for m in out["metrics"]]
    print(f"\narch={args.arch} steps={args.steps} "
          f"wall={out['wall_seconds']:.1f}s "
          f"ckpt_blocking={out['ckpt_blocking_seconds']:.2f}s")
    if losses:
        print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"metrics": out["metrics"],
                       "wall_seconds": out["wall_seconds"],
                       "ckpt_blocking_seconds": out["ckpt_blocking_seconds"]},
                      f, indent=1)


if __name__ == "__main__":
    main()
