"""Shared model components: configs, norms, RoPE/M-RoPE, MLPs, embeddings.

All modules are pure functions over explicit parameter pytrees (nested
dicts of jnp arrays) — no framework. Homogeneous layer stacks carry a
leading layer axis and are driven by ``jax.lax.scan`` to keep HLO size
independent of depth (essential for the 512-device CPU dry-run).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

Params = Dict[str, Any]


# ----------------------------------------------------------------------
# Config
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2 applies it:
    rotation pairs faster than ``beta_fast`` turns over the original
    context keep their frequency, those slower than ``beta_slow`` turns
    are divided by ``factor``, a linear ramp blends the pairs between,
    and the softmax scale is multiplied by ``mscale(mscale_all_dim)^2``."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 1e4
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    act: str = "swiglu"            # swiglu | gelu
    tie_embeddings: bool = False
    # --- MoE ---
    moe: bool = False
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    moe_every: int = 1             # MoE FFN on layers where i % moe_every == moe_offset
    moe_offset: int = 0
    first_dense_layers: int = 0    # leading layers with a dense MLP (d_ff)
    norm_topk_prob: bool = True    # renormalise the top-k gates to sum to 1
    expert_offset: int = 0         # routed experts held here: ids
    experts_held: int = 0          # [offset, offset + held); 0 -> all
    # --- MLA (deepseek) ---
    mla: bool = False
    kv_lora_rank: int = 0
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128
    yarn: Optional[Yarn] = None    # YaRN scaling of the rope (MLA)
    # --- SSM (mamba2) ---
    ssm: bool = False
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 64
    # --- hybrid (jamba) ---
    attn_period: int = 0           # attention at layers i % attn_period == attn_offset
    attn_offset: int = 0
    # --- enc-dec (whisper) ---
    encoder_decoder: bool = False
    n_enc_layers: int = 0
    enc_seq: int = 1500            # stub frontend: precomputed frame embeds
    # --- vlm (qwen2-vl) ---
    mrope: bool = False
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    n_patches: int = 0             # stub frontend: precomputed patch embeds
    # --- numerics / training ---
    compute_dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    remat: str = "full"            # full | dots | none
    logits_chunk: int = 0          # 0 = unchunked cross-entropy
    grad_accum: int = 1            # microbatch accumulation (memory knob)
    prefill_microbatch: int = 1    # chunked prefill (inference memory knob)
    sp_residual: bool = True       # sequence-parallel residual carry
    mla_absorb: bool = False       # absorbed-matmul MLA decode
    ctx_parallel: bool = False     # context-parallel attention (seq-
                                   # sharded q, replicated attn weights)
    ctx_replicate_weights: bool = True  # False: keep attn weights sharded
                                   # (transient per-layer gathers instead)
    cache_shard: str = "seq"       # decode-cache layout: seq|latent|heads
    unroll: bool = False           # unroll layer loops (dry-run delta method)
    # reduced-config smoke marker
    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        """Embedding tables padded to a multiple of 256 so the vocab dim
        shards evenly on any production mesh (Megatron-style padding;
        labels never reference the padded ids)."""
        return ((self.vocab + 255) // 256) * 256

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def is_attn_layer(self, i: int) -> bool:
        if not self.attn_period:
            return not self.ssm
        return i % self.attn_period == self.attn_offset

    @property
    def n_held_experts(self) -> int:
        return self.experts_held or self.n_experts

    def is_moe_layer(self, i: int) -> bool:
        return (self.moe and i >= self.first_dense_layers
                and i % self.moe_every == self.moe_offset)

    def scaled(self, **overrides) -> "ArchConfig":
        return dataclasses.replace(self, **overrides)


# ----------------------------------------------------------------------
# Initialisation helpers
# ----------------------------------------------------------------------
def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * 0.02).astype(dtype)


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------
def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
              eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_params(cfg: ArchConfig, d: int) -> Params:
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), cfg.pdtype),
                "bias": jnp.zeros((d,), cfg.pdtype)}
    return {"scale": jnp.ones((d,), cfg.pdtype)}


def apply_norm(cfg: ArchConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ----------------------------------------------------------------------
# RoPE / M-RoPE
# ----------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn: Yarn) -> np.ndarray:
    """Inverse frequencies of YaRN: pair ``i`` blends the original
    frequency (weight ``1 - ramp_i``) with it divided by ``factor``; the
    ramp runs linearly from 0 to 1 between the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    def pair_at(turns):
        return (head_dim * math.log(yarn.original_max_position
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(pair_at(yarn.beta_fast)), 0)
    hi = min(math.ceil(pair_at(yarn.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim)
    ramp = np.clip((np.arange(head_dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (extra / yarn.factor * ramp + extra * (1.0 - ramp)).astype(
        np.float32)


def yarn_attn_scale(yarn: Optional[Yarn]) -> float:
    """Factor on the attention softmax scale: ``mscale(mscale_all_dim)^2``."""
    if yarn is None or not yarn.mscale_all_dim:
        return 1.0
    return yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float,
               yarn: Optional[Yarn] = None) -> jnp.ndarray:
    """x: (b, h, s, d); pos: (b, s) int32 absolute positions."""
    d = x.shape[-1]
    if yarn is None:
        freqs = rope_freqs(d, theta)                   # (d/2,)
    else:
        freqs = jnp.asarray(yarn_freqs(d, theta, yarn))
    ang = pos[:, None, :, None].astype(jnp.float32) * freqs  # (b,1,s,d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, pos3: jnp.ndarray, theta: float,
                sections: Tuple[int, int, int]) -> jnp.ndarray:
    """Multimodal RoPE (qwen2-vl): pos3 (3, b, s) = (t, h, w) position ids.

    The head dim's frequency slots are partitioned into three sections, each
    rotated by its own position stream.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    # section assignment per frequency slot
    sec = np.concatenate([np.full(s, i) for i, s in enumerate(sections)])
    assert sec.shape[0] == d // 2, (sections, d)
    sec = jnp.asarray(sec)
    # pos per slot: (b, s, d/2) — slot j follows position stream sec[j]
    pos = pos3.transpose(1, 2, 0).astype(jnp.float32)[:, :, sec]
    ang = pos[:, None, :, :] * freqs                   # (b,1,s,d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq: int, d: int) -> jnp.ndarray:
    pos = np.arange(seq)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * i / d))
    emb = np.concatenate([np.sin(ang), np.cos(ang)], -1)
    return jnp.asarray(emb, jnp.float32)


# ----------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------
def mlp_params(cfg: ArchConfig, key, d: int, ff: int) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], (d, ff), 0, cfg.pdtype),
         "w2": dense_init(ks[1], (ff, d), 0, cfg.pdtype)}
    if cfg.act == "swiglu":
        p["w3"] = dense_init(ks[2], (d, ff), 0, cfg.pdtype)
    return p


def apply_mlp(cfg: ArchConfig, p: Params, x: jnp.ndarray,
              residual: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """MLP routed through ``ops.fused_mlp``: on the Pallas backends the
    activation, SwiGLU gate and the caller's residual add execute as GEMM
    store epilogues (one rounding, no extra HBM round trip); the ref
    backend keeps the original plain-jnp math. Passing ``residual``
    returns ``residual + mlp(x)`` so callers fuse their residual add."""
    dt = cfg.cdtype
    x = x.astype(dt)
    return ops.fused_mlp(
        x, p["w1"].astype(dt), p["w2"].astype(dt),
        w3=p["w3"].astype(dt) if cfg.act == "swiglu" else None,
        act=cfg.act, residual=residual)


# ----------------------------------------------------------------------
# Embedding / unembedding / loss
# ----------------------------------------------------------------------
def embed_params(cfg: ArchConfig, key) -> Params:
    k1, k2 = jax.random.split(key)
    v = cfg.padded_vocab
    p = {"embed": embed_init(k1, (v, cfg.d_model), cfg.pdtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, (cfg.d_model, v), 0, cfg.pdtype)
    return p


def embed_tokens(cfg: ArchConfig, p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return p["embed"].astype(cfg.cdtype)[tokens]


def unembed(cfg: ArchConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    w = (p["embed"].T if cfg.tie_embeddings else p["unembed"]).astype(cfg.cdtype)
    return x.astype(cfg.cdtype) @ w


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean next-token cross-entropy. logits (b, s, v); labels (b, s)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    nll = lse - ll
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def chunked_xent(cfg: ArchConfig, p: Params, h: jnp.ndarray,
                 labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Cross-entropy without materialising the full (b, s, v) logits.

    Splits the sequence axis into ``cfg.logits_chunk`` slices inside a scan:
    the unembed GEMM and the log-sum-exp are computed per chunk (an NTX
    MAX+MAC streaming reduction over the vocab stream).
    """
    if not cfg.logits_chunk or h.shape[1] % cfg.logits_chunk:
        return softmax_xent(unembed(cfg, p, h), labels, mask)
    b, s, d = h.shape
    nc = s // cfg.logits_chunk
    hc = h.reshape(b, nc, cfg.logits_chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, nc, cfg.logits_chunk).swapaxes(0, 1)
    mc = (mask.reshape(b, nc, cfg.logits_chunk).swapaxes(0, 1)
          if mask is not None else jnp.ones_like(lc, jnp.float32))

    def step(carry, inp):
        tot, cnt = carry
        hx, lx, mx = inp
        logits = unembed(cfg, p, hx).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, lx[..., None], -1)[..., 0]
        mx = mx.astype(jnp.float32)
        return (tot + ((lse - ll) * mx).sum(), cnt + mx.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.float32(0), jnp.float32(0)),
                                 (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


# ----------------------------------------------------------------------
# Activation-sharding context (set by the launch/runtime step builders)
# ----------------------------------------------------------------------
_ACT_SHARDING: Dict[str, Any] = {}


def set_activation_sharding(mesh=None, data_axes=(), model_axis=None):
    """Enable sequence-parallel residual sharding inside the layer scans.

    With full-remat, the dominant live state during training is the scan
    carry (the (b, s, d) residual stream saved once per period). Sharding
    its sequence axis over ``model_axis`` (Megatron-style SP) cuts that by
    the TP degree; XLA inserts the all-gather at the attention boundary.
    Called with no args to disable.
    """
    global _ACT_SHARDING
    if mesh is None:
        _ACT_SHARDING = {}
    else:
        _ACT_SHARDING = {"mesh": mesh, "data_axes": tuple(data_axes),
                         "model_axis": model_axis}


def sp_constrain(x: jnp.ndarray) -> jnp.ndarray:
    """Residual stream (b, s, d) -> sharded (data, model, None) when the
    context is set and the dims divide; identity otherwise."""
    info = _ACT_SHARDING
    if not info or x.ndim != 3:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = info["mesh"]
    nm = mesh.shape[info["model_axis"]]
    ndd = 1
    for a in info["data_axes"]:
        ndd *= mesh.shape[a]
    bspec = info["data_axes"] if x.shape[0] % ndd == 0 else None
    sspec = info["model_axis"] if x.shape[1] % nm == 0 else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(bspec, sspec, None)))


def ctx_constrain_q(x: jnp.ndarray) -> jnp.ndarray:
    """(b, h, s, d) -> sequence axis sharded over model, heads replicated
    (context-parallel attention)."""
    info = _ACT_SHARDING
    if not info or x.ndim != 4:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = info["mesh"]
    nm = mesh.shape[info["model_axis"]]
    ndd = 1
    for a in info["data_axes"]:
        ndd *= mesh.shape[a]
    if x.shape[2] % nm or x.shape[0] % ndd:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(info["data_axes"], None,
                                 info["model_axis"], None)))


def ctx_replicate_kv(x: jnp.ndarray) -> jnp.ndarray:
    info = _ACT_SHARDING
    if not info or x.ndim != 4:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = info["mesh"]
    ndd = 1
    for a in info["data_axes"]:
        ndd *= mesh.shape[a]
    b = info["data_axes"] if x.shape[0] % ndd == 0 else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(b, None, None, None)))


def scan_or_unroll(cfg: ArchConfig, body, carry, xs):
    """lax.scan, or an unrolled python loop when ``cfg.unroll`` (used by the
    dry-run's per-period cost delta method — see launch/dryrun.py)."""
    if not cfg.unroll:
        return jax.lax.scan(body, carry, xs)
    np_ = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(np_):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *ls: jnp.stack(ls), *ys)
    else:
        ys = None
    return carry, ys


def remat_wrap(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)
