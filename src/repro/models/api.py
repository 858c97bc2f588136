"""Unified model API: one entry point per arch, family-dispatched.

    model = Model(cfg)
    params = model.init(seed)
    loss, metrics = model.loss(params, batch)          # train
    logits, cache, fill = model.prefill(params, batch) # inference prefill
    cache = model.init_cache(batch_size, seq_len)
    logits, cache = model.decode(params, tokens, cache, fill)
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from .common import ArchConfig, Params
from . import transformer, encdec


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self._mod = encdec if cfg.encoder_decoder else transformer

    # -- parameters ----------------------------------------------------
    def init(self, seed: int = 0) -> Params:
        """Random params from ``seed``. Jitted, so each weight is drawn,
        scaled and cast in one fused pass: run eagerly, every stacked
        weight would first exist in float32 (a 16-layer granite-3-8b
        stack holds ~3.4 GB of float32 w1 alone)."""
        return jax.jit(self._mod.init_params, static_argnums=0)(
            self.cfg, seed)

    # -- training ------------------------------------------------------
    def loss(self, params: Params, batch: Dict[str, Any]):
        return self._mod.loss_fn(self.cfg, params, batch)

    # -- inference -----------------------------------------------------
    def init_cache(self, batch: int, seq: int, dtype=jnp.bfloat16):
        return self._mod.init_cache(self.cfg, batch, seq, dtype)

    def prefill(self, params: Params, batch: Dict[str, Any],
                cache_len: int | None = None):
        return self._mod.prefill(self.cfg, params, batch, cache_len)

    def decode(self, params: Params, tokens, cache, fill,
               absorbed_mla: bool = False):
        if self.cfg.encoder_decoder:
            return self._mod.decode_step(self.cfg, params, tokens, cache,
                                         fill)
        return self._mod.decode_step(self.cfg, params, tokens, cache, fill,
                                     absorbed_mla=absorbed_mla)
