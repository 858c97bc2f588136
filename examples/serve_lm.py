"""Serving example: batched prefill + decode with a KV cache, for any
assigned architecture's REDUCED config (mamba2/jamba exercise state caches).

Run: PYTHONPATH=src python examples/serve_lm.py --arch llama3-8b
     PYTHONPATH=src python examples/serve_lm.py --arch mamba2-1.3b
"""
import argparse

import numpy as np

from repro import configs
from repro.models import Model
from repro.runtime import Server, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=configs.ARCHS
                    + list(configs._ALIASES))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = configs.get_reduced(args.arch)
    if cfg.encoder_decoder or cfg.n_patches:
        raise SystemExit(f"{args.arch} needs frontend inputs — use "
                         "examples/multimodal_stub.py")
    model = Model(cfg)
    params = model.init(0)

    srv = Server(cfg, params, ServeConfig(
        max_seq=args.prompt_len + args.new_tokens + 8,
        max_new_tokens=args.new_tokens, eos_token=-1,
        temperature=args.temperature))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, args.prompt_len)
               for _ in range(args.batch)]
    out = srv.generate(prompts)
    print(f"arch {cfg.name} (reduced) | batch {args.batch} | "
          f"time to first tokens {out['prefill_s']*1e3:.0f} ms | "
          f"decode {out['decode_tok_per_s']:.1f} tok/s")
    for i, c in enumerate(out["completions"]):
        print(f"  req{i}: {c[:12]}{'...' if len(c) > 12 else ''}")


if __name__ == "__main__":
    main()
