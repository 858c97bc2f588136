"""Whisper-class encoder-decoder backbone.

The audio conv frontend is a STUB per the assignment: ``input_specs``
provides precomputed frame embeddings (batch, enc_seq, d_model) — what the
two conv layers would emit. Everything downstream (encoder self-attention,
decoder self+cross attention, LayerNorm, GELU MLPs) is real.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .common import (ArchConfig, Params, apply_mlp, apply_norm, embed_params,
                     embed_tokens, mlp_params, norm_params, remat_wrap,
                     scan_or_unroll, softmax_xent, sp_constrain, unembed,
                     chunked_xent)
from .common import sinusoidal_pos


def _sinusoidal_at(pos_ids: jnp.ndarray, d: int) -> jnp.ndarray:
    """Sinusoidal embeddings at (possibly traced) positions. (s,) -> (s, d)."""
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos_ids.astype(jnp.float32)[:, None] / (10000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
from . import attention as attn


def _enc_layer_params(cfg: ArchConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    return {"norm1": norm_params(cfg, cfg.d_model),
            "attn": attn.gqa_params(cfg, k1),
            "norm2": norm_params(cfg, cfg.d_model),
            "ffn": mlp_params(cfg, k2, cfg.d_model, cfg.d_ff)}


def _dec_layer_params(cfg: ArchConfig, key) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"norm1": norm_params(cfg, cfg.d_model),
            "self_attn": attn.gqa_params(cfg, k1),
            "norm_x": norm_params(cfg, cfg.d_model),
            "cross_attn": attn.gqa_params(cfg, k2),
            "norm2": norm_params(cfg, cfg.d_model),
            "ffn": mlp_params(cfg, k3, cfg.d_model, cfg.d_ff)}


def init_params(cfg: ArchConfig, seed: int = 0) -> Params:
    key = jax.random.PRNGKey(seed)
    ke, kd, kemb = jax.random.split(key, 3)
    enc_keys = jax.random.split(ke, cfg.n_enc_layers)
    dec_keys = jax.random.split(kd, cfg.n_layers)
    return {
        "embed": embed_params(cfg, kemb),
        "enc_layers": jax.vmap(lambda k: _enc_layer_params(cfg, k))(enc_keys),
        "dec_layers": jax.vmap(lambda k: _dec_layer_params(cfg, k))(dec_keys),
        "enc_norm": norm_params(cfg, cfg.d_model),
        "dec_norm": norm_params(cfg, cfg.d_model),
    }


def encode(cfg: ArchConfig, params: Params, enc_embeds: jnp.ndarray):
    """(b, s_enc, d) frame embeddings -> encoder states."""
    dt = cfg.cdtype
    b, s, d = enc_embeds.shape
    x = enc_embeds.astype(dt) + sinusoidal_pos(s, d).astype(dt)[None]

    def body(x, p):
        x = sp_constrain(x)
        h = apply_norm(cfg, p["norm1"], x)
        o, _ = attn.gqa_forward(cfg, p["attn"], h, pos=None, causal=False)
        x = x + o
        h = apply_norm(cfg, p["norm2"], x)
        return sp_constrain(apply_mlp(cfg, p["ffn"], h, residual=x)), None

    x, _ = scan_or_unroll(cfg, remat_wrap(cfg, body), x, params["enc_layers"])
    return apply_norm(cfg, params["enc_norm"], x)


def _cross_kv(cfg: ArchConfig, p: Params, enc: jnp.ndarray):
    dt = cfg.cdtype
    b, s, _ = enc.shape
    hd = cfg.hd
    k = (enc @ p["wk"].astype(dt)).reshape(b, s, cfg.n_kv_heads, hd)
    v = (enc @ p["wv"].astype(dt)).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt).reshape(1, 1, cfg.n_kv_heads, hd)
        v = v + p["bv"].astype(dt).reshape(1, 1, cfg.n_kv_heads, hd)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def _decoder(cfg: ArchConfig, params: Params, tokens, enc,
             fill=None, cache=None):
    """Shared decoder body. Training/prefill when cache is None."""
    dt = cfg.cdtype
    b, s = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens)
    start = 0 if fill is None else fill
    pos_ids = start + jnp.arange(s)
    x = x + _sinusoidal_at(pos_ids, cfg.d_model).astype(dt)[None]

    if cache is None:
        def body(x, p):
            x = sp_constrain(x)
            h = apply_norm(cfg, p["norm1"], x)
            o, _ = attn.gqa_forward(cfg, p["self_attn"], h, pos=None,
                                    causal=True)
            x = x + o
            h = apply_norm(cfg, p["norm_x"], x)
            kv = _cross_kv(cfg, p["cross_attn"], enc)
            o, _ = attn.gqa_forward(cfg, p["cross_attn"], h, pos=None,
                                    causal=False, kv=kv)
            x = x + o
            h = apply_norm(cfg, p["norm2"], x)
            return sp_constrain(apply_mlp(cfg, p["ffn"], h, residual=x)), None

        x, _ = scan_or_unroll(cfg, remat_wrap(cfg, body), x,
                              params["dec_layers"])
        return apply_norm(cfg, params["dec_norm"], x), None

    def body(x, scanned):
        p, c = scanned
        h = apply_norm(cfg, p["norm1"], x)
        o, kv_new = attn.gqa_decode(cfg, p["self_attn"], h, None, c, fill)
        x = x + o
        h = apply_norm(cfg, p["norm_x"], x)
        o, _ = attn.gqa_forward(cfg, p["cross_attn"], h, pos=None,
                                causal=False,
                                kv=(c["ck"].astype(dt), c["cv"].astype(dt)))
        x = x + o
        h = apply_norm(cfg, p["norm2"], x)
        x = apply_mlp(cfg, p["ffn"], h, residual=x)
        return x, attn.write_cache(c, kv_new, fill)

    x, new_cache = scan_or_unroll(cfg, body, x,
                                  (params["dec_layers"], cache))
    return apply_norm(cfg, params["dec_norm"], x), new_cache


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, Any]):
    enc = encode(cfg, params, batch["enc_embeds"])
    h, _ = _decoder(cfg, params, batch["tokens"], enc)
    loss = chunked_xent(cfg, params["embed"], h, batch["labels"],
                        batch.get("loss_mask"))
    return loss, {"xent": loss, "moe_aux": jnp.float32(0.0)}


def init_cache(cfg: ArchConfig, batch: int, seq: int, dtype=jnp.bfloat16):
    hd = cfg.hd
    L = cfg.n_layers
    return {"k": jnp.zeros((L, batch, cfg.n_kv_heads, seq, hd), dtype),
            "v": jnp.zeros((L, batch, cfg.n_kv_heads, seq, hd), dtype),
            "ck": jnp.zeros((L, batch, cfg.n_kv_heads, cfg.enc_seq, hd), dtype),
            "cv": jnp.zeros((L, batch, cfg.n_kv_heads, cfg.enc_seq, hd), dtype)}


def prefill(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            cache_len=None):
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache_len = cache_len or s
    enc = encode(cfg, params, batch["enc_embeds"])

    # decoder forward that also emits the cache layer-by-layer (scan ys)
    def body(x, p):
        dt = cfg.cdtype
        h1 = apply_norm(cfg, p["norm1"], x)
        o, (k, v) = attn.gqa_forward(cfg, p["self_attn"], h1, None, True)
        x = x + o
        h1 = apply_norm(cfg, p["norm_x"], x)
        ck, cv = _cross_kv(cfg, p["cross_attn"], enc)
        o, _ = attn.gqa_forward(cfg, p["cross_attn"], h1, None, False,
                                kv=(ck, cv))
        x = x + o
        h1 = apply_norm(cfg, p["norm2"], x)
        x = apply_mlp(cfg, p["ffn"], h1, residual=x)
        pad = lambda t: _pad(t, cache_len)
        return x, {"k": pad(k), "v": pad(v),
                   "ck": ck.astype(jnp.bfloat16), "cv": cv.astype(jnp.bfloat16)}

    dt = cfg.cdtype
    x = embed_tokens(cfg, params["embed"], tokens)
    x = x + _sinusoidal_at(jnp.arange(s), cfg.d_model).astype(dt)[None]
    x, cache = scan_or_unroll(cfg, remat_wrap(cfg, body), x,
                              params["dec_layers"])
    h = apply_norm(cfg, params["dec_norm"], x)
    logits = unembed(cfg, params["embed"], h[:, -1:])
    return logits[:, 0], cache, s


def _pad(t: jnp.ndarray, to: int) -> jnp.ndarray:
    cur = t.shape[2]
    if cur == to:
        return t.astype(jnp.bfloat16)
    w = [(0, 0)] * t.ndim
    w[2] = (0, to - cur)
    return jnp.pad(t, w).astype(jnp.bfloat16)


def decode_step(cfg: ArchConfig, params: Params, tokens, cache, fill,
                **_):
    h, new_cache = _decoder(cfg, params, tokens, None, fill=fill,
                            cache=cache)
    logits = unembed(cfg, params["embed"], h)
    return logits, new_cache
