"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --shape train_4k --steps 100 --ckpt-dir /tmp/ckpt [--smoke]

``--smoke`` swaps in the reduced config + a tiny shape so the full driver
(ckpt/restart/straggler machinery included) runs on one CPU device.  On a
real cluster the same entrypoint runs under the production mesh
(``--mesh single|multi``), with jax.distributed initialized by the
launcher environment.
"""

from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default="/tmp/widesa_ckpt")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single", "multi"])
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    from repro.configs import SHAPES, get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.configs.base import ShapeSpec
    from repro.train import Trainer, TrainConfig

    if args.smoke:
        cfg = get_smoke_config(args.arch)
        shape = ShapeSpec("smoke", "train", 64, 4)
    else:
        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]

    enable_compile_cache()
    mesh = None
    multi_pod = args.mesh == "multi"
    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=multi_pod)

    tcfg = TrainConfig(base_lr=args.lr, total_steps=max(args.steps, 1),
                       ckpt_every=max(args.steps // 4, 1))
    trainer = Trainer(cfg, shape, ckpt_dir=args.ckpt_dir, tcfg=tcfg,
                      mesh=mesh, multi_pod=multi_pod)
    trainer.install_signal_handlers()
    trainer.run(args.steps, resume=True)
    print("done")


if __name__ == "__main__":
    main()
