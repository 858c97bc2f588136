"""What the launchers and ``chip_smoke.py`` share: the model-config flags
(``--arch``, ``--reduced``, ``--set``) and JAX's persistent compilation
cache. Nothing here imports jax at module level: ``launch.train`` parses
its flags before jax initialises."""
from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Dict, Sequence

#: root of the checkout (src/repro/launch/common.py -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is set here. Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, so a later process of the same checkout finds what an
    earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def add_config_args(ap: argparse.ArgumentParser, default_arch: str) -> None:
    ap.add_argument("--arch", default=default_arch)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--set", nargs="*", default=[], metavar="KEY=VALUE",
                    help="ArchConfig overrides, e.g. n_layers=16")


def parse_overrides(pairs: Sequence[str]) -> Dict[str, object]:
    """``key=value`` strings -> typed ArchConfig overrides (int, float,
    true/false, else the string)."""
    out: Dict[str, object] = {}
    for kv in pairs:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                v = {"true": True, "false": False}.get(v.lower(), v)
        out[k] = v
    return out


def config_from_args(args: argparse.Namespace):
    """The ArchConfig the flags select: published (or reduced) widths of
    ``--arch`` with the ``--set`` overrides applied."""
    from repro import configs
    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get(args.arch))
    overrides = parse_overrides(args.set)
    return cfg.scaled(**overrides) if overrides else cfg
