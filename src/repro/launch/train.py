"""Training launcher CLI.

Single-host CPU (tests/examples):
  PYTHONPATH=src python -m repro.launch.train --arch mamba-130m \
      --preset tiny --steps 100

Production mesh (TPU pod or the 512-fake-device dry environment):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b \
      --mesh single --global-batch 256 --seq 4096 ...

On a real multi-host TPU deployment this process runs once per host after
``jax.distributed.initialize()``; the data pipeline shards by
(process_index, process_count) and the checkpoint manager writes per-host
shards — both already structured for that (see their docstrings).
"""
import argparse
import dataclasses

import jax

from repro import configs
from repro.launch import compile_cache
from repro.optim import AdamWConfig
from repro.parallel import sharding
from repro.runtime.train_loop import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--weight-decay", type=float, default=0.1)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--moment-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single", "multi", "local"])
    ap.add_argument("--scan-impl", default=None)
    ap.add_argument("--dtype", default=None)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.get_config(args.arch)
    over = {}
    if args.scan_impl:
        over["scan_impl"] = args.scan_impl
    if args.dtype:
        over["dtype"] = args.dtype
    if over:
        cfg = dataclasses.replace(cfg, **over)

    mesh = None
    rules = None
    if args.mesh in ("single", "multi"):
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        rules = sharding.ShardingRules(act_embed="model")
    elif args.mesh == "local":
        from repro.launch.mesh import make_local_mesh
        n = jax.device_count()
        mesh = make_local_mesh((max(n // 2, 1), min(2, n)),
                               ("data", "model"))
        rules = sharding.ShardingRules()

    tcfg = TrainConfig(
        total_steps=args.steps, warmup_steps=args.warmup,
        global_batch=args.global_batch, seq_len=args.seq,
        ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
        log_every=args.log_every, grad_accum=args.grad_accum,
        grad_compression=args.grad_compression,
        optimizer=AdamWConfig(lr=args.lr, weight_decay=args.weight_decay,
                              moment_dtype=args.moment_dtype))
    trainer = Trainer(cfg, tcfg, mesh=mesh, rules=rules)
    _, _, losses = trainer.run(resume=not args.no_resume)
    print(f"[launch.train] {args.arch}: loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f} ({len(losses)} steps)")


if __name__ == "__main__":
    main()
