"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
        --reduced --steps 50 --ckpt-dir /tmp/ckpt [--autotune]

On a real TPU cluster this process runs once per host (jax.distributed
initializes from the environment); the CPU container runs the same code
single-host. Checkpoints are elastic: restarts may use a different mesh.
Tuning knobs are the canonical ``repro.tune`` flag set
(:meth:`repro.TuningConfig.add_flags`); the train loop drives them
through one :class:`repro.TuningSession`.
"""

import argparse


def main() -> None:
    # repro.api is jax-free: --help and flag errors stay fast; the
    # jax-heavy loop modules load only after parsing succeeds
    from repro.api import TuningConfig, train_tuning_defaults

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (recovery demo)")
    base = train_tuning_defaults()
    TuningConfig.add_flags(ap, base=base)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.runtime.train_loop import TrainLoopConfig, train

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeSpec("cli", "train", args.seq, args.batch)
    loop = TrainLoopConfig(
        steps=args.steps, ckpt_every=max(args.steps // 10, 1),
        ckpt_dir=args.ckpt_dir,
        compress_grads=args.compress_grads, fail_at_step=args.fail_at,
        tuning=TuningConfig.from_flags(args, base=base))
    out = train(cfg, shape, loop)
    print({k: v for k, v in out.items() if k != "losses"})


if __name__ == "__main__":
    main()
