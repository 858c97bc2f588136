"""Attention blocks: GQA (llama-class) and MLA (deepseek-v2 class).

Both expose the same entry points:
  * ``*_params(cfg, key)``                      parameter pytree
  * ``*_forward(cfg, p, x, pos[, kv])``         training / prefill; returns
                                                (out, cache_entry)
  * ``*_decode(cfg, p, x, pos, cache, fill)``   single/few-token decode over
                                                a pre-allocated cache that
                                                holds positions < fill;
                                                returns (out, this step's
                                                entries), which the caller
                                                stores with ``write_cache``

Attention math runs through ``repro.kernels.ops.attention`` — the NTX
MAX+MAC streaming reduction (flash kernel on TPU, oracle on CPU).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import mla_decode as mla_decode_kernel, ops
from .common import (ArchConfig, Params, apply_rope, apply_mrope,
                     ctx_constrain_q, ctx_replicate_kv, dense_init,
                     yarn_attn_scale)


# ----------------------------------------------------------------------
# GQA
# ----------------------------------------------------------------------
def gqa_params(cfg: ArchConfig, key) -> Params:
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d, cfg.n_heads * hd), 0, cfg.pdtype),
         "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd), 0, cfg.pdtype),
         "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd), 0, cfg.pdtype),
         "wo": dense_init(ks[3], (cfg.n_heads * hd, d), 0, cfg.pdtype)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), cfg.pdtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.pdtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.pdtype)
    return p


def _qkv(cfg: ArchConfig, p: Params, x: jnp.ndarray):
    dt = cfg.cdtype
    b, s, _ = x.shape
    hd = cfg.hd
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    return q, k, v


def _rope_qk(cfg: ArchConfig, q, k, pos):
    if cfg.mrope:
        q = apply_mrope(q, pos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, pos, cfg.rope_theta, cfg.mrope_sections)
    elif pos is not None:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, k


def gqa_forward(cfg: ArchConfig, p: Params, x: jnp.ndarray,
                pos, causal: bool = True,
                kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None):
    """Self- or cross-attention over a full sequence.

    ``kv``: (k, v) already in (b, hkv, s, hd) layout for cross-attention
    (whisper decoder -> encoder); otherwise computed from x.
    Returns (out, (k, v)) so prefill can populate a cache.
    """
    dt = cfg.cdtype
    b, s, _ = x.shape
    if kv is None:
        q, k, v = _qkv(cfg, p, x)
        q, k = _rope_qk(cfg, q, k, pos)
        if cfg.ctx_parallel:
            # context parallelism: local q sequence shard attends over the
            # all-gathered (replicated) K/V — per-layer wire bytes drop from
            # the 2x d_model-wide ARs to the (much smaller, GQA) K+V gather
            q = ctx_constrain_q(q)
            k = ctx_replicate_kv(k)
            v = ctx_replicate_kv(v)
    else:
        q = (x @ p["wq"].astype(dt))
        if cfg.qkv_bias:
            q = q + p["bq"].astype(dt)
        q = q.reshape(b, s, cfg.n_heads, cfg.hd).transpose(0, 2, 1, 3)
        k, v = kv
    o = ops.attention(q, k, v, causal=causal)
    if kv is None and cfg.ctx_parallel:
        o = ctx_constrain_q(o)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    return o @ p["wo"].astype(dt), (k, v)


def gqa_init_cache(cfg: ArchConfig, batch: int, seq: int, dtype) -> Params:
    hd = cfg.hd
    return {"k": jnp.zeros((batch, cfg.n_kv_heads, seq, hd), dtype),
            "v": jnp.zeros((batch, cfg.n_kv_heads, seq, hd), dtype)}


def gqa_decode(cfg: ArchConfig, p: Params, x: jnp.ndarray, pos,
               cache: Params, fill: jnp.ndarray):
    """x: (b, s_new, d); cache k/v (b, hkv, S, hd); fill = current length.
    Returns (out, {"k", "v"}: (b, hkv, s_new, hd))."""
    dt = cfg.cdtype
    b, s, _ = x.shape
    q, k_new, v_new = _qkv(cfg, p, x)
    q, k_new = _rope_qk(cfg, q, k_new, pos)
    new = {"k": k_new, "v": v_new}
    c = write_cache(cache, new, fill)
    o = ops.attention(q, c["k"].astype(dt), c["v"].astype(dt), causal=True,
                      kv_len=fill + s)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    return o @ p["wo"].astype(dt), new


def write_cache(cache: Params, new: Params, fill, layer=0) -> Params:
    """``cache`` with ``new`` (entries from ``*_decode``, of the same rank)
    written from sequence position ``fill``: one dynamic_update_slice a
    leaf, in place where ``cache`` is donated. Every cache leaf has its
    sequence axis second to last; ``layer`` offsets the leading axis, the
    first of the layers ``new`` holds where ``cache`` is a stack."""
    out = dict(cache)
    for name, n in new.items():
        start = [layer] + [0] * (n.ndim - 1)
        start[-2] = fill
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], n.astype(cache[name].dtype), start)
    return out


# ----------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek-v2)
# ----------------------------------------------------------------------
def mla_params(cfg: ArchConfig, key) -> Params:
    d = cfg.d_model
    r = cfg.kv_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    h = cfg.n_heads
    ks = jax.random.split(key, 6)
    return {
        # queries: per-head nope + rope parts (no q compression in v2-lite)
        "wq": dense_init(ks[0], (d, h * (dn + dr)), 0, cfg.pdtype),
        # joint KV compression + the shared rope key
        "wdkv": dense_init(ks[1], (d, r + dr), 0, cfg.pdtype),
        # up-projections from the latent
        "wuk": dense_init(ks[2], (r, h * dn), 0, cfg.pdtype),
        "wuv": dense_init(ks[3], (r, h * dv), 0, cfg.pdtype),
        "wo": dense_init(ks[4], (h * dv, d), 0, cfg.pdtype),
        "kv_norm": jnp.ones((r,), cfg.pdtype),
    }


def _mla_qkv(cfg: ArchConfig, p: Params, x: jnp.ndarray, pos):
    from .common import rmsnorm
    dt = cfg.cdtype
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank

    q = (x @ p["wq"].astype(dt)).reshape(b, s, h, dn + dr).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta, cfg.yarn)

    ckv = x @ p["wdkv"].astype(dt)                 # (b, s, r + dr)
    c_kv, k_rope = ckv[..., :r], ckv[..., r:]
    c_kv = rmsnorm(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[:, None], pos, cfg.rope_theta,
                        cfg.yarn)                      # (b,1,s,dr)
    return q_nope, q_rope, c_kv, k_rope


def _mla_scale(cfg: ArchConfig) -> float:
    """Softmax scale over the (nope + rope) query-key width, with YaRN's
    ``mscale^2`` where the rope is scaled."""
    return ((cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
            * yarn_attn_scale(cfg.yarn))


def _mla_attend(cfg: ArchConfig, p: Params, q_nope, q_rope, c_kv, k_rope,
                kv_len=None):
    """Expanded-form MLA attention (baseline; absorbed form is the
    decode-path optimization, see mla_decode_absorbed)."""
    dt = cfg.cdtype
    b, s = q_nope.shape[0], q_nope.shape[2]
    skv = c_kv.shape[1]
    h = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim

    # expand latent to per-head keys/values
    k_nope = (c_kv @ p["wuk"].astype(dt)).reshape(b, skv, h, dn).transpose(0, 2, 1, 3)
    v = (c_kv @ p["wuv"].astype(dt)).reshape(b, skv, h, dv).transpose(0, 2, 1, 3)
    k_rope_b = jnp.broadcast_to(k_rope, (b, h, skv, dr))

    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope_b], -1)
    o = ops.attention(q, k, v, causal=True, scale=_mla_scale(cfg),
                      kv_len=kv_len)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return o @ p["wo"].astype(dt)


def mla_forward(cfg: ArchConfig, p: Params, x: jnp.ndarray, pos,
                causal: bool = True, kv=None):
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, p, x, pos)
    with jax.named_scope("mla.attend"):
        out = _mla_attend(cfg, p, q_nope, q_rope, c_kv, k_rope)
    return out, (c_kv, k_rope)


def mla_init_cache(cfg: ArchConfig, batch: int, seq: int, dtype) -> Params:
    return {"c_kv": jnp.zeros((batch, seq, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, 1, seq, cfg.rope_head_dim), dtype)}


def mla_decode(cfg: ArchConfig, p: Params, x: jnp.ndarray, pos,
               cache: Params, fill: jnp.ndarray, absorbed: bool = False,
               layer=None):
    """Returns (out, {"c_kv": (b, s_new, r), "k_rope": (b, 1, s_new, dr)}).
    The absorbed form attends over the cache and the new entries as they
    are, its ``cache`` the kind's stacked caches and ``layer`` this
    layer's index in them; the expanded form over a copy of the layer's
    ``cache`` with them written."""
    dt = cfg.cdtype
    s = x.shape[1]
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(cfg, p, x, pos)
    new = {"c_kv": c_kv_new, "k_rope": k_rope_new}
    with jax.named_scope("mla.attend"):
        if absorbed:
            out = _mla_attend_absorbed(cfg, p, q_nope, q_rope, cache, new,
                                       fill, layer)
        else:
            c = write_cache(cache, new, fill)
            out = _mla_attend(cfg, p, q_nope, q_rope, c["c_kv"].astype(dt),
                              c["k_rope"].astype(dt), kv_len=fill + s)
    return out, new


def mla_onepass_fits(cfg: ArchConfig, batch: int, s: int, seq: int,
                     cache_dtype=jnp.bfloat16) -> bool:
    """Whether the absorbed decode of ``s`` new tokens a row over a
    ``batch`` x ``seq`` cache takes the one-pass kernel on a TPU
    (``kernels/mla_decode.py``): one token, queries in the cache's
    dtype, and a cache the kernel's blocks tile."""
    return (s == 1 and jnp.dtype(cfg.cdtype) == jnp.dtype(cache_dtype)
            and mla_decode_kernel.blocks(batch, seq, cfg.kv_lora_rank,
                                         cfg.rope_head_dim) is not None)


def _mla_attend_absorbed(cfg: ArchConfig, p: Params, q_nope, q_rope,
                         stack: Params, new: Params, fill, layer):
    """Absorbed-matmul MLA decode (beyond-paper §Perf optimization).

    Instead of expanding the latent cache to per-head K/V (which costs
    2 * skv * h * (dn+dv) * r flops per step), absorb W_uk into the query
    and W_uv into the output: attention runs directly in the r-dim latent
    space. Decode flops drop from O(skv*h*(dn+dv)*r) to O(skv*h*(r+dr)) per
    query — the memory term drops by ~h x as well since the latent is read
    once instead of h expanded heads.

    The keys are layer ``layer`` of the kind's ``stack`` at positions <
    ``fill`` and this step's entries ``new``, causally, each read where it
    lies: no copy of the cache is made. Lowered for a TPU at shapes
    ``mla_onepass_fits`` takes, one Pallas pass reads the layer's
    positions < ``fill`` for the scores and the values alike; elsewhere
    the jnp form makes one einsum of each over the layer's whole cache.
    """
    dt = cfg.cdtype
    b, h, s, dn = q_nope.shape
    r = cfg.kv_lora_rank
    dv = cfg.v_head_dim

    wuk = p["wuk"].astype(dt).reshape(r, h, dn)
    # q_lat[b,h,s,r] = q_nope . wuk^T  (absorb the key up-projection)
    q_lat = jnp.einsum("bhsd,rhd->bhsr", q_nope, wuk)

    def jnp_form():
        one = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, layer, 0, keepdims=False), stack)
        return _latent_jnp(cfg, q_lat, q_rope, one, new, fill)

    c_kv = stack["c_kv"]
    if mla_onepass_fits(cfg, b, s, c_kv.shape[2], c_kv.dtype):
        o_lat = jax.lax.platform_dependent(tpu=lambda: _latent_onepass(
            cfg, q_lat, q_rope, stack, new, fill, layer), default=jnp_form)
    else:
        o_lat = jnp_form()
    wuv = p["wuv"].astype(dt).reshape(r, h, dv)
    o = jnp.einsum("bhsr,rhd->bhsd", o_lat, wuv)        # absorb W_uv
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    return o @ p["wo"].astype(dt)


def _latent_scores(cfg: ArchConfig, q_lat, q_rope, c, k):
    """Scores of the latent and rope queries against keys ``c`` (b, k, r)
    and ``k`` (b, 1, k, dr), in float32 as the expanded form's."""
    f32 = jnp.float32
    return (jnp.einsum("bhsr,bkr->bhsk", q_lat, c, preferred_element_type=f32)
            + jnp.einsum("bhsd,bkd->bhsk", q_rope, k[:, 0],
                         preferred_element_type=f32)) * _mla_scale(cfg)


def _new_entries(cfg: ArchConfig, cache: Params, new: Params):
    """This step's entries rounded as the cache will hold them."""
    return (new[n].astype(cache[n].dtype).astype(cfg.cdtype)
            for n in ("c_kv", "k_rope"))


def _latent_jnp(cfg: ArchConfig, q_lat, q_rope, cache: Params, new: Params,
                fill):
    """The attention in the latent space, (b, h, s, r): the scores and
    the values each one einsum over one layer's whole cache."""
    dt = cfg.cdtype
    s = q_lat.shape[2]
    skv = cache["c_kv"].shape[1]
    c_kv, k_rope = cache["c_kv"].astype(dt), cache["k_rope"].astype(dt)
    c_new, k_new = _new_entries(cfg, cache, new)
    f32 = jnp.float32
    old = jnp.where(jnp.arange(skv) < fill,
                    _latent_scores(cfg, q_lat, q_rope, c_kv, k_rope),
                    -jnp.inf)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    cur = jnp.where(causal, _latent_scores(cfg, q_lat, q_rope, c_new, k_new),
                    -jnp.inf)
    # one softmax over both key sets, each kept where it lies
    m = jnp.maximum(old.max(-1, keepdims=True), cur.max(-1, keepdims=True))
    e_old, e_cur = jnp.exp(old - m), jnp.exp(cur - m)
    z = e_old.sum(-1, keepdims=True) + e_cur.sum(-1, keepdims=True)
    return (jnp.einsum("bhsk,bkr->bhsr", (e_old / z).astype(dt), c_kv,
                       preferred_element_type=f32)
            + jnp.einsum("bhsk,bkr->bhsr", (e_cur / z).astype(dt), c_new,
                         preferred_element_type=f32)).astype(dt)


def _latent_onepass(cfg: ArchConfig, q_lat, q_rope, stack: Params,
                    new: Params, fill, layer, interpret: bool = False):
    """``_latent_jnp`` for one new token a row with the cache read once:
    the one-pass kernel over layer ``layer`` of the kind's ``stack``, then
    this step's own entry merged under the same softmax."""
    dt = cfg.cdtype
    acc, m_old, l_old = mla_decode_kernel.mla_decode_onepass(
        q_lat[:, :, 0], q_rope[:, :, 0], stack["c_kv"], stack["k_rope"],
        layer, fill, scale=_mla_scale(cfg), interpret=interpret)
    c_new, k_new = _new_entries(cfg, stack, new)
    cur = _latent_scores(cfg, q_lat, q_rope, c_new, k_new)[:, :, 0]
    m = jnp.maximum(m_old, cur)                              # (b, h, 1)
    a, e = jnp.exp(m_old - m), jnp.exp(cur - m)
    o = (a * acc + jnp.einsum("bhk,bkr->bhr", e.astype(dt), c_new,
                              preferred_element_type=jnp.float32)
         ) / (a * l_old + e)
    return o[:, :, None].astype(dt)
