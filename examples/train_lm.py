"""End-to-end driver: train a ~100M-param llama-family model for a few
hundred steps on synthetic data, with checkpointing and auto-resume
(assignment deliverable b — the training-kind end-to-end example).

Run:       PYTHONPATH=src python examples/train_lm.py [--steps 300]
Resume:    re-run the same command — it restarts from the last checkpoint.
Multi-dev: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
           PYTHONPATH=src python examples/train_lm.py --mesh 4x2
"""
import argparse

import jax

from repro.models import ArchConfig
from repro.optim import AdamWConfig
from repro.runtime import TrainConfig, Trainer


def model_100m() -> ArchConfig:
    """~100M llama-family config (GQA, SwiGLU, RoPE)."""
    return ArchConfig(name="llama-100m", family="dense",
                      n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                      d_ff=2048, vocab=32768, rope_theta=1e4)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--mesh", default=None,
                    help="DxM data x model mesh, e.g. 4x2 (needs devices)")
    args = ap.parse_args()

    cfg = model_100m()
    n_params = None
    mesh = None
    if args.mesh:
        d, m = map(int, args.mesh.split("x"))
        from repro.distributed.mesh import make_mesh
        mesh = make_mesh((d, m), ("data", "model"))
        from repro.models.common import set_activation_sharding
        set_activation_sharding(mesh, ("data",), "model")

    trainer = Trainer(
        cfg,
        AdamWConfig(lr=3e-4, warmup_steps=30, total_steps=args.steps,
                    weight_decay=0.01),
        TrainConfig(steps=args.steps, log_every=10, ckpt_every=50,
                    ckpt_dir=args.ckpt, global_batch=args.batch,
                    seq_len=args.seq),
        mesh=mesh)
    n_params = sum(x.size for x in jax.tree.leaves(trainer.model.init(0)))
    print(f"model: {cfg.name} ({n_params/1e6:.1f}M params), "
          f"batch {args.batch} x seq {args.seq}, {args.steps} steps")
    result = trainer.run()
    ls = result["losses"]
    print(f"loss: {ls[0]:.3f} -> {ls[-1]:.3f} over {len(ls)} steps "
          f"(stragglers={result['straggler_events']}, "
          f"resumed_from={result['resumed_from']})")
    ms = result.get("multistream")
    if ms:
        # the optimizer update planned as an ntx.Program across the mesh
        print(f"update plan: {ms['n_substreams']} per-tensor streams on "
              f"{ms['n_clusters']} clusters, model speedup "
              f"{ms['model_speedup']:.2f}x (pipelined "
              f"{ms['pipeline']['model_speedup']:.2f}x)")
    assert ls[-1] < ls[0], "loss must decrease"


if __name__ == "__main__":
    main()
