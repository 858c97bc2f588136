"""Plain float32 forward of a DeepSeek-V2 decoder, one layer at a time, at
``highest`` matmul precision.

The layer, written out from the published description (arXiv:2405.04434
and the model's ``config.json``):

* pre-norm RMSNorm before attention and before the FFN;
* MLA, expanded: queries ``wq`` (no compression), the joint latent and
  rope key ``wdkv``; the latent goes through its own RMSNorm and up to
  per-head keys (``wuk``) and values (``wuv``); the rope key is shared by
  all heads. Rope on the query's and key's rope parts uses YaRN's
  frequencies, and the softmax scale is ``(nope + rope)^-0.5`` times
  YaRN's ``mscale(mscale_all_dim)^2``;
* the first ``first_k_dense_replace`` layers have a dense SwiGLU MLP;
  the others route each token by softmax over all ``n_routed_experts``
  (published count) to its greedy top ``num_experts_per_tok``, gates as
  the softmax gives them unless ``norm_topk_prob``, times
  ``routed_scaling_factor``; the held experts' part of the routed sum
  (each held expert a SwiGLU MLP, computed for every token and weighted
  by its gate where the token chose it, else by 0) plus the shared
  experts' SwiGLU MLP;
* a final RMSNorm and an untied unembedding.

It scores served tokens as ``lm_f32`` does: for each request the forward
runs once over the prompt and the served tokens (teacher forcing), and at
each served position it reads how far the served token's logit lies below
the best logit there. With ``control=True`` every projection, MLP,
router, expert and unembedding matmul also runs with both operands
rounded to float8 (e4m3, scaled per tensor), beside the float32 forward.
The weights are made again from the seed (``mla_moe_weights``), a layer
at a time.
"""
from __future__ import annotations

import json
import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference.mla_moe_weights as weights
from reference.lm_f32 import HIGHEST, _gaps, _mm, _rmsnorm


def yarn_inv_freq(cfg: Dict) -> np.ndarray:
    """YaRN's inverse frequencies of the rope pairs (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``), float64."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    ys = cfg["rope_scaling"]
    orig = ys["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / ys["factor"]
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - keep) + extra * keep


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg: Dict) -> float:
    ys = cfg["rope_scaling"]
    m = _mscale(ys["factor"], ys["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, inv_freq, mag):
    """x: (t, heads, d); split-half pairs, positions 0..t-1."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, w1, w2, w3, fp8):
    return _mm(jax.nn.silu(_mm(h, w1, fp8)) * _mm(h, w3, fp8), w2, fp8)


def _attention(cfg, w, h, fp8):
    t = h.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    ys = cfg["rope_scaling"]
    inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    mag = _mscale(ys["factor"], ys["mscale"]) / _mscale(ys["factor"],
                                                        ys["mscale_all_dim"])
    q = _mm(h, w["mixer/wq"], fp8).reshape(t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv, mag)], -1)
    ckv = _mm(h, w["mixer/wdkv"], fp8)
    c = _rmsnorm(ckv[:, :r], cfg["rms_norm_eps"])
    k_rope = _rope(ckv[:, None, r:], inv, mag)                 # (t, 1, dr)
    k_nope = _mm(c, w["mixer/wuk"], fp8).reshape(t, nh, dn)
    v = _mm(c, w["mixer/wuv"], fp8).reshape(t, nh, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (t, nh, dr))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    s = s * softmax_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return _mm(o.reshape(t, nh * dv), w["mixer/wo"], fp8)


def routed_gates(cfg: Dict, router, h, fp8=False):
    """(t, experts) gate of each expert for each token: its softmax score
    where the token chose it among its top ``num_experts_per_tok``, else
    0 (renormalised over the chosen ones only with ``norm_topk_prob``)."""
    probs = jax.nn.softmax(_mm(h, router, fp8), -1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * cfg["routed_scaling_factor"]
    return jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)


def moe_ffn(cfg: Dict, w, h, fp8=False):
    """The held experts' part of the routed sum plus the shared experts."""
    gates = routed_gates(cfg, w["ffn/router"], h, fp8)
    y = _swiglu(h, w["ffn/shared/w1"], w["ffn/shared/w2"],
                w["ffn/shared/w3"], fp8)
    for j, e in enumerate(weights.held(cfg)):
        y = y + gates[:, e:e + 1] * _swiglu(h, w["ffn/w1"][j],
                                            w["ffn/w2"][j],
                                            w["ffn/w3"][j], fp8)
    return y


@partial(jax.jit, static_argnames=("cfg", "moe", "fp8"))
def _layer(cfg, w, x, moe, fp8=False):
    cfg = json.loads(cfg)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, w, _rmsnorm(x, eps), fp8)
    h = _rmsnorm(x, eps)
    if moe:
        return x + moe_ffn(cfg, w, h, fp8)
    return x + _swiglu(h, w["ffn/w1"], w["ffn/w2"], w["ffn/w3"], fp8)


@partial(jax.jit, static_argnames=("cfg", "n_out", "fp8"))
def _logits(cfg, unembed, x, n_out, fp8=False):
    cfg = json.loads(cfg)
    h = _rmsnorm(x[-n_out:], cfg["rms_norm_eps"])
    return _mm(h, unembed[:, :cfg["vocab_size"]].astype(jnp.float32), fp8)


def forward(cfg: Dict, key, requests: Sequence[Tuple[np.ndarray,
                                                      np.ndarray]],
            control: bool = False) -> Dict[str, list]:
    """Per request, the logits at the positions that chose its served
    tokens: float32 (``"ref"``) and, with ``control``, float8
    (``"control"``). ``requests``: (prompt ids, served ids) pairs; the
    forward runs over the prompt and all served tokens but the last."""
    frozen = json.dumps(cfg, sort_keys=True)      # a jit static argument
    emb = jax.jit(partial(weights.embeddings, cfg))(key)
    streams = {False: [], True: []} if control else {False: []}
    for prompt, served in requests:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        x0 = emb["embed"][jnp.asarray(seq)].astype(jnp.float32)
        for fp8 in streams:
            streams[fp8].append(x0)
    makers = {moe: jax.jit(lambda k, i, moe=moe: weights.layer(cfg, k, i,
                                                               moe))
              for moe in (False, True)}
    for i in range(cfg["num_hidden_layers"]):
        moe = i >= weights.n_dense(cfg)
        w = makers[moe](key, i)
        for fp8, xs in streams.items():
            streams[fp8] = [_layer(frozen, w, x, moe, fp8=fp8) for x in xs]
        del w
    return {"ref" if not fp8 else "control": [
        _logits(frozen, emb["unembed"], x, len(served), fp8=fp8)
        for x, (_, served) in zip(xs, requests)]
        for fp8, xs in streams.items()}


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
