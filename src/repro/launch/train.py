"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Builds the mesh for whatever devices exist (elastic.make_mesh), the
pjit'd train step with the production sharding rules, and runs the
fault-tolerant host loop (checkpoint/auto-resume, straggler monitor,
optional gradient compression). On this CPU container use ``--smoke``
to run the reduced config of the same family end-to-end.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.elastic import make_mesh
from repro.runtime.train_loop import TrainSetup, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress", default=None, choices=[None, "int8", "elp4"])
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    mesh = make_mesh(target_model=args.model_parallel) if len(jax.devices()) > 1 else None
    print(f"arch={cfg.name} params={cfg.param_count() / 1e6:.1f}M mesh={mesh and mesh.shape}")

    setup = TrainSetup(
        cfg=cfg,
        mesh=mesh,
        lr_peak=args.lr,
        warmup=max(args.steps // 10, 5),
        total_steps=args.steps,
        remat=True,
        compress=args.compress,
        seq_parallel=args.seq_parallel,
        moe_impl="ep" if mesh is not None else "dense",
    )
    out = train(
        setup,
        steps=args.steps,
        batch_size=args.batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt_dir,
    )
    print(f"final loss {out['losses'][-1]:.4f}; straggler {out['straggler_report']}")


if __name__ == "__main__":
    main()
