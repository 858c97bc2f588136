"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Serves the published widths of ``--arch`` (``--reduced`` for the CPU smoke
config, ``--set key=value`` to override any ArchConfig field, e.g.
``--set n_layers=16`` to cut depth). Loads params from a checkpoint
directory if given (CheckpointManager layout) — a restore that fails is
an error — otherwise serves random-init weights made from ``--seed``.
"""
import argparse
import sys

import numpy as np


def _parse(argv=None) -> argparse.Namespace:
    from repro.launch.common import add_config_args
    ap = argparse.ArgumentParser()
    add_config_args(ap, default_arch="llama3-8b")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    return ap.parse_args(argv)


def load_server(args: argparse.Namespace):
    """(cfg, Server) for parsed launcher flags: the selected config, its
    params (restored from ``--ckpt`` or initialised from ``--seed``) and a
    cache sized for ``--prompt-len`` + ``--new-tokens``."""
    from repro.launch.common import config_from_args
    from repro.models import Model
    from repro.runtime import Server, ServeConfig

    cfg = config_from_args(args)
    if cfg.encoder_decoder or cfg.n_patches:
        raise SystemExit(f"{args.arch} needs frontend inputs — see "
                         "examples/multimodal_stub.py")
    params = Model(cfg).init(args.seed)
    if args.ckpt:
        from repro.checkpoint import CheckpointManager
        restored, step = CheckpointManager(args.ckpt).restore(
            {"params": params})
        params = restored["params"]
        print(f"restored params from step {step}")
    srv = Server(cfg, params, ServeConfig(
        max_seq=args.prompt_len + args.new_tokens + 8,
        max_new_tokens=args.new_tokens, eos_token=-1,
        temperature=args.temperature, seed=args.seed))
    return cfg, srv


def main(argv=None):
    from repro.launch.common import enable_compile_cache
    args = _parse(argv)
    enable_compile_cache()
    cfg, srv = load_server(args)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab, args.prompt_len)
               for _ in range(args.batch)]
    out = srv.generate(prompts)
    print(f"time to first tokens {out['prefill_s']*1e3:.0f} ms | "
          f"decode {out['decode_tok_per_s']:.1f} tok/s")
    for i, c in enumerate(out["completions"]):
        print(f"req{i}: {c}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
