"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Production entry point wiring the arch registry, mesh construction, the
activation-sharding context, fault-tolerant Trainer and checkpointing.
On real TPU pods the same flags run under the TPU runtime's device set;
on CPU hosts use --devices N to emulate a small mesh (set before jax
initialises, which is why this module parses argv before importing jax).
"""
import argparse
import os
import sys


def _parse(argv=None):
    from repro.launch.common import add_config_args
    ap = argparse.ArgumentParser()
    add_config_args(ap, default_arch="llama3-8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--devices", type=int, default=0,
                    help="emulate N host devices (CPU only)")
    ap.add_argument("--mesh", default=None, help="DxM, e.g. 4x2")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse(argv)
    if args.devices:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count"
                                   f"={args.devices}")
    from repro.launch.common import config_from_args, enable_compile_cache
    from repro.optim import AdamWConfig
    from repro.runtime import TrainConfig, Trainer

    enable_compile_cache()
    cfg = config_from_args(args)

    mesh = None
    if args.mesh:
        d, m = map(int, args.mesh.split("x"))
        from repro.distributed.mesh import make_mesh
        mesh = make_mesh((d, m), ("data", "model"))
        from repro.models.common import set_activation_sharding
        set_activation_sharding(mesh, ("data",), "model")

    trainer = Trainer(
        cfg,
        AdamWConfig(lr=args.lr, warmup_steps=max(10, args.steps // 10),
                    total_steps=args.steps),
        TrainConfig(steps=args.steps, log_every=10,
                    ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt,
                    resume=args.resume, global_batch=args.global_batch,
                    seq_len=args.seq),
        mesh=mesh)
    r = trainer.run()
    print(f"done: loss {r['losses'][0]:.3f} -> {r['losses'][-1]:.3f}, "
          f"stragglers={r['straggler_events']}, bad={r['bad_steps']}, "
          f"resumed_from={r['resumed_from']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
