"""Generic decoder-only LM covering all assigned decoder families.

Layers come in *kinds* (attn/ssm mixer x moe/mlp/none ffn): jamba's 1:7
attention:mamba interleave, periodic MoE, deepseek's leading dense layer.
Parameters and caches are stored per kind, stacked over that kind's
layers. One function, ``_layer``, applies a layer of any kind; training,
prefill and decode differ only in the mixer they hand it.

Serving walks ``layer_segments`` (the leading dense layers, then the
repeating rest): one ``lax.scan`` a segment, one period of kinds unrolled
in its body, so no branch over kinds. A branch returns every kind's cache,
so each layer would copy the stacks of the kinds it does not own. Prefill
writes each layer's cache entry into its kind's stack inside the scan;
decode's scans only read the caches, and each segment's new positions are
written in place after it.

Training scans one ``lax.switch`` over the kinds instead (``backbone``),
for the memory of its backward pass.

Entry points: init / loss / prefill / decode_step — see ``api.py``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import (ArchConfig, Params, apply_mlp, apply_norm, chunked_xent,
                     embed_params, embed_tokens, mlp_params, norm_params,
                     remat_wrap, scan_or_unroll, sp_constrain, unembed)
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod


# ----------------------------------------------------------------------
# Layer schedule
# ----------------------------------------------------------------------
def period_len(cfg: ArchConfig) -> int:
    """Shortest period of the layer-kind pattern (dry-run delta method)."""
    p = 1
    if cfg.attn_period:
        p = cfg.attn_period
    if cfg.moe and cfg.moe_every > 1:
        p = math.lcm(p, cfg.moe_every)
    return p


def n_periods(cfg: ArchConfig) -> int:
    p = period_len(cfg)
    assert cfg.n_layers % p == 0, (cfg.n_layers, p)
    return cfg.n_layers // p


def _kind_of(cfg: ArchConfig, i: int) -> str:
    mixer = "attn" if cfg.is_attn_layer(i) else "ssm"
    if cfg.is_moe_layer(i):
        ffn = "moe"
    elif cfg.d_ff:
        ffn = "mlp"
    else:
        ffn = "none"
    return f"{mixer}_{ffn}"


def layer_schedule(cfg: ArchConfig):
    """Returns (sched, kinds, idx_in_kind): per-layer kind name, the ordered
    unique kinds, and each layer's index within its kind's stack."""
    sched = [_kind_of(cfg, i) for i in range(cfg.n_layers)]
    kinds = list(dict.fromkeys(sched))
    counters = {k: 0 for k in kinds}
    idx_in_kind: List[int] = []
    for k in sched:
        idx_in_kind.append(counters[k])
        counters[k] += 1
    return sched, kinds, idx_in_kind


def layer_segments(cfg: ArchConfig):
    """The layer schedule as the runs prefill and decode scan: the leading
    ``first_dense_layers``, then the rest, each split into repeats of its
    shortest period of kinds. Returns [(period's kinds, idx_in_kind of
    shape (repeats, len(period)))]; each kind's layers in a run are
    consecutive in its stack."""
    sched, _, idx_in_kind = layer_schedule(cfg)
    fd = cfg.first_dense_layers
    segments = []
    for lo, hi in ((0, fd), (fd, cfg.n_layers)):
        run = sched[lo:hi]
        if not run:
            continue
        p = next(p for p in range(1, len(run) + 1)
                 if run == run[:p] * (len(run) // p))
        segments.append((run[:p], np.asarray(idx_in_kind[lo:hi],
                                             np.int32).reshape(-1, p)))
    return segments


# ----------------------------------------------------------------------
# Parameters
# ----------------------------------------------------------------------
def _layer_params(cfg: ArchConfig, key, kind: str) -> Params:
    mixer, ffn = kind.split("_")
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {"norm1": norm_params(cfg, cfg.d_model)}
    if ffn != "none":
        p["norm2"] = norm_params(cfg, cfg.d_model)
    if mixer == "attn":
        p["mixer"] = (attn.mla_params(cfg, k1) if cfg.mla
                      else attn.gqa_params(cfg, k1))
    else:
        p["mixer"] = ssm_mod.ssm_params(cfg, k1)
    if ffn == "moe":
        p["ffn"] = moe_mod.moe_params(cfg, k2)
    elif ffn == "mlp":
        p["ffn"] = mlp_params(cfg, k3, cfg.d_model, cfg.d_ff)
    return p


def init_params(cfg: ArchConfig, seed: int = 0) -> Params:
    key = jax.random.PRNGKey(seed)
    k_emb, k_layers = jax.random.split(key)
    sched, kinds, _ = layer_schedule(cfg)
    layers: Dict[str, Params] = {}
    for kind in kinds:
        count = sum(1 for k in sched if k == kind)
        keys = jax.random.split(
            jax.random.fold_in(k_layers, kinds.index(kind)), count)
        layers[kind] = jax.vmap(lambda kk: _layer_params(cfg, kk, kind))(keys)
    params = {"embed": embed_params(cfg, k_emb),
              "layers": layers,
              "final_norm": norm_params(cfg, cfg.d_model)}
    if cfg.n_patches:
        params["img_proj"] = jnp.eye(cfg.d_model, dtype=cfg.pdtype)
    return params


def _index_tree(tree: Params, idx) -> Params:
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
        tree)


def _update_tree(tree: Params, new: Params, idx) -> Params:
    return jax.tree.map(
        lambda full, n: jax.lax.dynamic_update_index_in_dim(
            full, n.astype(full.dtype), idx, 0), tree, new)


# ----------------------------------------------------------------------
# One layer
# ----------------------------------------------------------------------
def _layer(cfg: ArchConfig, kind: str, p: Params, x, mixer, aux, load):
    """One layer of ``kind``: norm, ``mixer(h) -> (out, cache entry)``,
    residual, norm, the kind's FFN. Returns (x, entry, aux, load); a MoE
    layer adds its balance loss to ``aux`` and its held experts'
    assignment counts to ``load`` (left None where there is no count)."""
    ffn = kind.split("_")[1]
    o, entry = mixer(apply_norm(cfg, p["norm1"], x))
    x = x + o
    if ffn == "none":
        return x, entry, aux, load
    h = apply_norm(cfg, p["norm2"], x)
    if ffn == "mlp":
        # residual add fused into the MLP's second-GEMM store epilogue
        return apply_mlp(cfg, p["ffn"], h, residual=x), entry, aux, load
    o, a, n = moe_mod.apply_moe(cfg, p["ffn"], h)
    return x + o, entry, aux + a, None if load is None else load + n


def _mixer_forward(cfg: ArchConfig, kind: str, p, h, pos):
    """Full-sequence mixer for training; it keeps no cache entry."""
    if kind.startswith("attn"):
        fwd = attn.mla_forward if cfg.mla else attn.gqa_forward
        return fwd(cfg, p["mixer"], h, pos)[0], None
    return ssm_mod.ssm_forward(cfg, p["mixer"], h), None


def _mixer_prefill(cfg: ArchConfig, kind: str, p, h, pos, cache_len: int):
    """Full-sequence mixer that also returns this layer's cache entry."""
    if kind.startswith("attn"):
        if cfg.mla:
            o, (c_kv, k_rope) = attn.mla_forward(cfg, p["mixer"], h, pos)
            c = {"c_kv": _pad_seq(c_kv, cache_len, 1),
                 "k_rope": _pad_seq(k_rope, cache_len, 2)}
        else:
            o, (k, v) = attn.gqa_forward(cfg, p["mixer"], h, pos)
            c = {"k": _pad_seq(k, cache_len, 2),
                 "v": _pad_seq(v, cache_len, 2)}
        return o, c
    return ssm_mod.ssm_forward(cfg, p["mixer"], h, return_state=True)


def _mixer_decode(cfg: ArchConfig, kind: str, p, h, pos, c, fill,
                  absorbed_mla: bool, layer=None):
    """(out, this step's entries): new positions for attention, whole
    states for a state-space layer (they have no sequence axis). ``c`` is
    the layer's cache, or its kind's stack where ``layer`` is given."""
    if kind.startswith("attn"):
        if cfg.mla:
            return attn.mla_decode(cfg, p["mixer"], h, pos, c, fill,
                                   absorbed=absorbed_mla, layer=layer)
        return attn.gqa_decode(cfg, p["mixer"], h, pos, c, fill)
    return ssm_mod.ssm_decode(cfg, p["mixer"], h, c)


# ----------------------------------------------------------------------
# Forward (training)
# ----------------------------------------------------------------------
def backbone(cfg: ArchConfig, params: Params, x: jnp.ndarray,
             pos) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Embedded inputs -> final hidden states. Returns (h, moe_aux_loss).

    Homogeneous stacks (9 of 10 assigned archs): one ``lax.scan`` over the
    stacked layer params — the memory-optimal structure (XLA's backward
    keeps one scan body's working set live).

    Heterogeneous stacks (jamba, deepseek's dense first layer): one scan
    whose body ``lax.switch``-es over the layer kinds, not serving's walk
    of ``layer_segments``. Compiled ``jax.grad`` of the loss on the CPU
    (reduced configs, batch 4, ``grad_accum`` 1; temporaries of the switch
    scan -> a segment walk with each layer rematerialised, its weights
    indexed inside the checkpoint): jamba 107.8 -> 326.7 MB at sequence 256
    and 539.8 -> 1366.9 MB at 1024; deepseek 61.5 -> 79.9 MB and 431.8 ->
    516.9 MB. The remaining fit lever is gradient accumulation
    (cfg.grad_accum), which divides all activation transients.
    """
    sp = sp_constrain if cfg.sp_residual else (lambda t: t)
    sched, kinds, idx_in_kind = layer_schedule(cfg)
    layers = params["layers"]

    def apply(kind, p, x, aux):
        x, _, aux, _ = _layer(
            cfg, kind, p, x, lambda h: _mixer_forward(cfg, kind, p, h, pos),
            aux, None)
        return x, aux

    if cfg.unroll:
        aux = jnp.float32(0.0)
        for i, kind in enumerate(sched):
            p = _index_tree(layers[kind], idx_in_kind[i])
            # remat (with the production policy) kept in the unrolled
            # delta-method variant so measured flops/bytes include the
            # production recompute behaviour
            x, aux = remat_wrap(cfg, functools.partial(apply, kind))(
                p, x, aux)
        return apply_norm(cfg, params["final_norm"], x), aux

    if len(kinds) == 1:
        kind = kinds[0]

        def body(carry, p):
            x, aux = carry
            x = sp(x)                  # SP: carry sharded (data, model, -)
            x, aux = apply(kind, p, x, aux)
            return (sp(x), aux), None

        body = remat_wrap(cfg, body)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), layers[kind])
        return apply_norm(cfg, params["final_norm"], x), aux

    kind_ids = jnp.asarray([kinds.index(k) for k in sched], jnp.int32)
    idxs = jnp.asarray(idx_in_kind, jnp.int32)

    def branch(kind, x, aux, idx):
        return apply(kind, _index_tree(layers[kind], idx), x, aux)

    branches = [functools.partial(branch, k) for k in kinds]

    def body(carry, step):
        x, aux = carry
        kid, idx = step
        x = sp(x)
        x, aux = jax.lax.switch(kid, branches, x, aux, idx)
        return (sp(x), aux), None

    body = remat_wrap(cfg, body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), (kind_ids, idxs))
    return apply_norm(cfg, params["final_norm"], x), aux


def embed_inputs(cfg: ArchConfig, params: Params, batch: Dict[str, Any]):
    """Token embedding (+ VLM patch stub: first n_patches positions come
    from precomputed patch embeddings)."""
    x = embed_tokens(cfg, params["embed"], batch["tokens"])
    if cfg.n_patches:
        img = batch["img_embeds"].astype(cfg.cdtype) @ \
            params["img_proj"].astype(cfg.cdtype)
        x = jnp.concatenate([img, x[:, cfg.n_patches:]], 1)
    return x


def positions(cfg: ArchConfig, batch: Dict[str, Any]) -> jnp.ndarray:
    b, s = batch["tokens"].shape
    if cfg.mrope:
        if "pos3" in batch:
            return batch["pos3"]
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        return jnp.broadcast_to(pos[None], (3, b, s))
    return jnp.broadcast_to(jnp.arange(s)[None], (b, s))


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, Any]):
    x = embed_inputs(cfg, params, batch)
    pos = positions(cfg, batch)
    h, aux = backbone(cfg, params, x, pos)
    mask = batch.get("loss_mask")
    loss = chunked_xent(cfg, params["embed"], h, batch["labels"], mask)
    total = loss + 0.01 * aux
    return total, {"xent": loss, "moe_aux": aux}


# ----------------------------------------------------------------------
# KV / state cache + decode / prefill
# ----------------------------------------------------------------------
def _kind_cache(cfg: ArchConfig, kind: str, batch: int, seq: int, dtype):
    mixer = kind.split("_")[0]
    if mixer == "attn":
        return (attn.mla_init_cache(cfg, batch, seq, dtype) if cfg.mla
                else attn.gqa_init_cache(cfg, batch, seq, dtype))
    return ssm_mod.ssm_init_cache(cfg, batch, dtype)


def init_cache(cfg: ArchConfig, batch: int, seq: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Per layer kind, its layers' caches stacked. MoE configs also carry
    ``moe_load`` (int32, one per held expert): the assignments each held
    expert received, summed over layers and over the prefill and every
    decode step that used the cache, on the device."""
    sched, kinds, _ = layer_schedule(cfg)
    caches = {}
    for kind in kinds:
        count = sum(1 for k in sched if k == kind)
        one = _kind_cache(cfg, kind, batch, seq, dtype)
        caches[kind] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one)
    if cfg.moe:
        caches["moe_load"] = jnp.zeros((cfg.n_held_experts,), jnp.int32)
    return caches


def _walk_segments(cfg: ArchConfig, layer_fn, carry):
    """Every layer in schedule order: per segment of ``layer_segments``, one
    ``scan_or_unroll`` over its rows of ``idx_in_kind``, one period of
    layers unrolled in the body. ``layer_fn(kind, idx, carry) -> (carry,
    out)``. Returns (carry, [(period, idxs, outs)]), each segment's outs
    listed by position in the period and stacked over its repeats."""
    segments = []
    for period, idxs in layer_segments(cfg):
        def body(carry, row, period=period):
            outs = []
            for j, kind in enumerate(period):
                carry, out = layer_fn(kind, row[j], carry)
                outs.append(out)
            return carry, outs

        carry, outs = scan_or_unroll(cfg, body, carry, idxs)
        segments.append((period, idxs, outs))
    return carry, segments


def decode_step(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
                cache: Dict[str, Any], fill: jnp.ndarray,
                absorbed_mla: bool = False):
    """tokens: (b, s_new) -> (logits (b, s_new, vocab), new cache).

    The walk of ``layer_segments`` only reads the cache stacks; each
    layer's new entries leave its scan as an output, and one
    dynamic_update_slice a leaf stores a segment's entries in place.
    Written inside the scan, the stacks would be carried, and the compiler
    lays a carried stack out for the small write: a copy of every stack in
    and out of the loop and of every slot it reads. The absorbed MLA
    mixer takes its kind's whole stack and the layer's index: its one-pass
    kernel reads the layer where it lies, with no slice of the stack."""
    x = embed_tokens(cfg, params["embed"], tokens)
    pos = positions(cfg, {"tokens": tokens}) + fill
    layers = params["layers"]
    cache = dict(cache)

    def layer(kind, idx, carry):
        x, load = carry
        p = _index_tree(layers[kind], idx)
        whole = absorbed_mla and cfg.mla and kind.startswith("attn")
        c = cache[kind] if whole else _index_tree(cache[kind], idx)
        x, new, _, load = _layer(
            cfg, kind, p, x,
            lambda h: _mixer_decode(cfg, kind, p, h, pos, c, fill,
                                    absorbed_mla, idx if whole else None),
            0.0, load)
        return (x, load), new

    # moe_load is None without MoE layers
    (x, load), segments = _walk_segments(cfg, layer,
                                         (x, cache.get("moe_load")))
    for period, idxs, new in segments:
        for kind in dict.fromkeys(period):
            at = [j for j, k in enumerate(period) if k == kind]
            # (repeats, len(at), ...) -> the kind's layers in stack order
            n = jax.tree.map(lambda *a: jnp.stack(a, 1).reshape(
                (-1,) + a[0].shape[1:]), *[new[j] for j in at])
            first = int(idxs[0, at[0]])
            if kind.startswith("attn"):
                cache[kind] = attn.write_cache(cache[kind], n, fill, first)
            else:
                cache[kind] = jax.tree.map(
                    lambda full, a: jax.lax.dynamic_update_slice_in_dim(
                        full, a.astype(full.dtype), first, 0), cache[kind], n)

    if cfg.moe:
        cache["moe_load"] = load
    h = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params["embed"], h)
    return logits, cache


def decode_onepass_layers(cfg: ArchConfig, batch: int, seq: int,
                          platform: str, absorbed_mla: bool) -> int:
    """How many layers of a one-token ``decode_step`` over a ``batch`` x
    ``seq`` bf16 cache, lowered for ``platform``, take the one-pass MLA
    kernel: every attention layer of an absorbed MLA step lowered for a
    TPU at shapes the kernel takes, else none."""
    if not (platform == "tpu" and absorbed_mla and cfg.mla
            and attn.mla_onepass_fits(cfg, batch, 1, seq)):
        return 0
    return sum(k.startswith("attn") for k in layer_schedule(cfg)[0])


def prefill(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            cache_len: Optional[int] = None):
    """Full-sequence forward that also fills the cache.

    Returns (last-position logits, cache, fill). The request batch is
    processed in ``cfg.prefill_microbatch`` sequential chunks where that
    divides it (serving-style chunked prefill: peak activation memory over
    the chunk count at unchanged total compute), else as one chunk."""
    b, s = batch["tokens"].shape
    mb = max(1, cfg.prefill_microbatch)
    logits, cache = _prefill_chunked(cfg, params, batch, cache_len or s,
                                     mb if b % mb == 0 else 1)
    return logits, cache, s


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _prefill_chunked(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
                     cache_len: int, mb: int):
    """``_prefill_impl`` of ``mb`` chunks of the batch, one after another,
    each chunk's cache written into one whole-batch cache in place.
    Returns (logits, cache)."""
    b = batch["tokens"].shape[0]
    bc = b // mb

    def split(path, leaf):
        if getattr(path[-1], "key", None) == "pos3":
            x = leaf.reshape(leaf.shape[0], mb, bc, *leaf.shape[2:])
            return jnp.moveaxis(x, 1, 0)
        return leaf.reshape(mb, bc, *leaf.shape[1:])

    def body(cache, step):
        i, chunk = step
        logits, part = _prefill_impl(cfg, params, chunk, cache_len)
        cache = dict(cache)
        for name, new in part.items():
            if name == "moe_load":
                cache[name] = cache[name] + new
            else:
                cache[name] = jax.tree.map(
                    lambda full, n: jax.lax.dynamic_update_slice_in_dim(
                        full, n, i * bc, axis=1), cache[name], new)
        return cache, logits

    chunks = jax.tree_util.tree_map_with_path(split, batch)
    cache, logits = jax.lax.scan(body, init_cache(cfg, b, cache_len),
                                 (jnp.arange(mb), chunks))
    return logits.reshape(b, -1), cache


def _prefill_impl(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
                  cache_len: int):
    """One chunk: the walk of ``layer_segments``, each layer writing its
    cache entry into its own kind's stack of the carried chunk cache.
    Returns (last-position logits, cache)."""
    x = embed_inputs(cfg, params, batch)
    pos = positions(cfg, batch)
    layers = params["layers"]
    sp = sp_constrain if cfg.sp_residual else (lambda t: t)

    def layer(kind, idx, carry):
        x, caches = carry
        p = _index_tree(layers[kind], idx)
        x, entry, _, load = _layer(
            cfg, kind, p, sp(x),
            lambda h: _mixer_prefill(cfg, kind, p, h, pos, cache_len),
            0.0, caches.get("moe_load"))
        caches = dict(caches)
        caches[kind] = _update_tree(caches[kind], entry, idx)
        if cfg.moe:
            caches["moe_load"] = load
        return (sp(x), caches), None

    b = batch["tokens"].shape[0]
    (x, caches), _ = _walk_segments(cfg, layer,
                                    (x, init_cache(cfg, b, cache_len)))
    h = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(cfg, params["embed"], h[:, -1:])
    return logits[:, 0], caches


def _pad_seq(x: jnp.ndarray, to: int, axis: int) -> jnp.ndarray:
    cur = x.shape[axis]
    if cur == to:
        return x.astype(jnp.bfloat16)
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, to - cur)
    return jnp.pad(x, widths).astype(jnp.bfloat16)
