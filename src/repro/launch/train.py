"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --reduced --steps 30 --seq 128 --batch 8 --ckpt-dir /tmp/ckpt

Full-config multi-host launches use the same entry point with
``--mesh production``; on this CPU box the production mesh is validated
via the dry-run instead (repro.launch.dryrun).
"""
import argparse

from repro.configs import get_config, get_reduced
from repro.configs.base import RunConfig, ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.runtime.train_loop import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default="host",
                    choices=["host", "production", "production-multi"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.mesh == "host":
        mesh = make_host_mesh(1, 1)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh.endswith("multi"))
    run = RunConfig(model=cfg,
                    shape=ShapeConfig("train", "train", args.seq,
                                      args.batch),
                    multi_pod=args.mesh.endswith("multi"),
                    remat=args.remat, optimizer=args.optimizer,
                    gradient_compression=args.compress_grads)
    tr = Trainer(run, mesh, TrainerConfig(
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        lr_base=args.lr, lr_warmup=max(args.steps // 10, 2),
        lr_total=max(args.steps, 100)))
    out = tr.train(args.steps)
    print(f"[{cfg.name}] {len(out['losses'])} steps, "
          f"loss {out['losses'][0]:.4f} -> {out['final_loss']:.4f}, "
          f"stragglers={len(out['stragglers'])}, "
          f"checkpoints={tr.ckpt.all_steps()}")


if __name__ == "__main__":
    main()
