"""Plain float32 forward of a Qwen2 decoder LM (pre-norm RMSNorm, GQA
attention with rotary positions and query, key and value biases, SwiGLU
MLP, untied unembedding), one layer at a time, at ``highest`` matmul
precision.

It scores served tokens: for each request, the forward runs once over the
prompt and the served tokens (teacher forcing), and at each served
position it reads how far the served token's logit lies below the best
logit there. Greedy decoding computed exactly reads 0 everywhere.

The weights are made again from the seed (``lm_weights``), layer by layer,
so the reference needs one layer's weights and the requests' activations
on the device at a time. With ``control=True`` every projection, MLP and
unembedding matmul also runs with both operands rounded to float8 (e4m3,
scaled per tensor), and the gap of the token that float8 puts first is
read beside the served one: the precision a later change could be tempted
to serve in.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference.lm_weights as weights

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(a):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def _mm(a, b, fp8: bool):
    if not fp8:
        return jnp.matmul(a, b, precision=HIGHEST)
    (qa, sa), (qb, sb) = _fp8(a), _fp8(b)
    return jnp.matmul(qa, qb, precision=HIGHEST) / (sa * sb)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (t, heads, hd); rotate-half convention, positions 0..t-1."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


@partial(jax.jit, static_argnames=("cfg", "fp8"))
def _layer(cfg, w, x, fp8=False):
    cfg = dict(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // hq
    eps = cfg["rms_norm_eps"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    t = x.shape[0]
    h = _rmsnorm(x, eps)
    q = _rope((_mm(h, w["mixer/wq"], fp8) + w["mixer/bq"]).reshape(t, hq, hd),
              cfg["rope_theta"])
    k = _rope((_mm(h, w["mixer/wk"], fp8) + w["mixer/bk"]).reshape(t, hkv, hd),
              cfg["rope_theta"])
    v = (_mm(h, w["mixer/wv"], fp8) + w["mixer/bv"]).reshape(t, hkv, hd)
    # query head j reads key/value head j // (hq // hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    s = s * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    x = x + _mm(o.reshape(t, hq * hd), w["mixer/wo"], fp8)
    h = _rmsnorm(x, eps)
    g = jax.nn.silu(_mm(h, w["ffn/w1"], fp8)) * _mm(h, w["ffn/w3"], fp8)
    return x + _mm(g, w["ffn/w2"], fp8)


@partial(jax.jit, static_argnames=("cfg", "n_out", "fp8"))
def _logits(cfg, unembed, x, n_out, fp8=False):
    cfg = dict(cfg)
    h = _rmsnorm(x[-n_out:], cfg["rms_norm_eps"])
    w = unembed[:, :cfg["vocab_size"]].astype(jnp.float32)
    return _mm(h, w, fp8)


def _gaps(ref_logits, tokens) -> np.ndarray:
    best = jnp.max(ref_logits, -1)
    return np.asarray(best - jnp.take_along_axis(
        ref_logits, jnp.asarray(tokens)[:, None], -1)[:, 0])


def forward(cfg: Dict, key, requests: Sequence[Tuple[np.ndarray,
                                                      np.ndarray]],
            control: bool = False) -> Dict[str, list]:
    """Per request, the logits at the positions that chose its served
    tokens: float32 (``"ref"``) and, with ``control``, float8
    (``"control"``). ``requests``: (prompt ids, served ids) pairs; the
    forward runs over the prompt and all served tokens but the last."""
    key_cfg = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, str))))
    emb = jax.jit(partial(weights.embeddings, cfg))(key)
    streams = {False: [], True: []} if control else {False: []}
    for prompt, served in requests:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        x0 = emb["embed"][jnp.asarray(seq)].astype(jnp.float32)
        for fp8 in streams:
            streams[fp8].append(x0)
    make_layer = jax.jit(partial(weights.layer, cfg))
    for i in range(cfg["num_hidden_layers"]):
        w = make_layer(key, i)
        for fp8, xs in streams.items():
            streams[fp8] = [_layer(key_cfg, w, x, fp8=fp8) for x in xs]
        del w
    out = {"ref" if not fp8 else "control": [
        _logits(key_cfg, emb["unembed"], x, len(served), fp8=fp8)
        for x, (_, served) in zip(xs, requests)]
        for fp8, xs in streams.items()}
    return out


def served_gaps(cfg: Dict, key, requests: Sequence[Tuple[np.ndarray,
                                                          np.ndarray]],
                control: bool = False) -> Dict[str, List[np.ndarray]]:
    """Per request, the gap below the float32 reference's best logit of
    each served token (``"served"``) and, with ``control``, of the token
    that the float8 forward puts first (``"control"``)."""
    logits = forward(cfg, key, requests, control)
    out = {"served": [_gaps(ref, served) for ref, (_, served)
                      in zip(logits["ref"], requests)]}
    if control:
        out["control"] = [_gaps(ref, np.asarray(jnp.argmax(low, -1)))
                          for ref, low in zip(logits["ref"],
                                              logits["control"])]
    return out
