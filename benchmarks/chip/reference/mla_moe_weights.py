"""Random weights of a DeepSeek-V2 decoder (MLA, a leading dense MLP, then
MoE layers with shared experts), made from a seed by the benchmark.

Drawn as ``lm_weights`` draws them: every matrix is a normal draw from
``fold_in(fold_in(key, layer), crc32(name))`` scaled by 1/sqrt(fan-in)
(the embedding table by 1), cast to the served dtype; norm scales are 1.
A routed expert's matrices are drawn from its global expert id as well
(``fold_in`` once more), so a chip holding experts ``[offset, offset +
held)`` gets the same experts as the whole model would, and the shares of
all chips make up the whole layer. The tree follows the layout the
serving program stores: per layer kind (``attn_mlp`` for the leading
dense layers, ``attn_moe`` for the rest) one stack of that kind's layers,
the held experts stacked inside each MoE layer, and the embedding and the
unembedding padded to a multiple of 256 rows/columns.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from reference.lm_weights import _draw, padded_vocab


def routed_experts(cfg: Dict) -> int:
    """The router's width: the published expert count (the file's own
    ``n_routed_experts`` counts the experts held here)."""
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def held(cfg: Dict) -> range:
    """Global ids of the routed experts this chip holds."""
    off = cfg.get("expert_offset", 0)
    return range(off, off + cfg["n_routed_experts"])


def n_dense(cfg: Dict) -> int:
    return cfg["first_k_dense_replace"]


def attn_shapes(cfg: Dict) -> Dict[str, tuple]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return {"mixer/wq": (d, h * (dn + dr)), "mixer/wdkv": (d, r + dr),
            "mixer/wuk": (r, h * dn), "mixer/wuv": (r, h * dv),
            "mixer/wo": (h * dv, d)}


def dense_shapes(cfg: Dict) -> Dict[str, tuple]:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return {"ffn/w1": (d, ff), "ffn/w2": (ff, d), "ffn/w3": (d, ff)}


def expert_shapes(cfg: Dict) -> Dict[str, tuple]:
    """One routed expert's matrices."""
    d, ffe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"ffn/w1": (d, ffe), "ffn/w2": (ffe, d), "ffn/w3": (d, ffe)}


def moe_shapes(cfg: Dict) -> Dict[str, tuple]:
    """An MoE layer's matrices outside the routed experts."""
    d = cfg["hidden_size"]
    ffs = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    return {"ffn/router": (d, routed_experts(cfg)),
            "ffn/shared/w1": (d, ffs), "ffn/shared/w2": (ffs, d),
            "ffn/shared/w3": (d, ffs)}


def layer(cfg: Dict, key, index, moe: bool) -> Dict[str, jnp.ndarray]:
    """Weights of layer ``index``, flat (``"mixer/wq"``, ...; no norms);
    an MoE layer's routed experts stacked as ``ffn/w1`` (held, d, ffe)."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    k = jax.random.fold_in(key, index)
    draw = lambda kk, name, shape: _draw(kk, name, shape, shape[0] ** -0.5,
                                         dtype)
    out = {n: draw(k, n, s) for n, s in attn_shapes(cfg).items()}
    if not moe:
        out.update({n: draw(k, n, s) for n, s in dense_shapes(cfg).items()})
        return out
    out.update({n: draw(k, n, s) for n, s in moe_shapes(cfg).items()})
    ids = jnp.asarray(list(held(cfg)))
    for n, s in expert_shapes(cfg).items():
        out[n] = jax.vmap(lambda e: draw(jax.random.fold_in(k, e), n, s))(ids)
    return out


def embeddings(cfg: Dict, key) -> Dict[str, jnp.ndarray]:
    dtype = jnp.dtype(cfg["torch_dtype"])
    d, vp = cfg["hidden_size"], padded_vocab(cfg)
    return {"embed": _draw(key, "embed", (vp, d), 1.0, dtype),
            "unembed": _draw(key, "unembed", (d, vp), d ** -0.5, dtype)}


def _stack(cfg: Dict, flat: Dict, n: int) -> Dict:
    dtype = jnp.dtype(cfg["torch_dtype"])
    d, r = cfg["hidden_size"], cfg["kv_lora_rank"]
    out: Dict = {"norm1": {"scale": jnp.ones((n, d), dtype)},
                 "norm2": {"scale": jnp.ones((n, d), dtype)}}
    for name, arr in flat.items():
        node = out
        *groups, leaf = name.split("/")
        for g in groups:
            node = node.setdefault(g, {})
        node[leaf] = arr
    out["mixer"]["kv_norm"] = jnp.ones((n, r), dtype)
    return out


def params(cfg: Dict, key) -> Dict:
    """The whole tree, in the program's layout (call under ``jax.jit``)."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    nd, n = n_dense(cfg), cfg["num_hidden_layers"]
    dense = jax.vmap(lambda i: layer(cfg, key, i, False))(jnp.arange(nd))
    moe = jax.vmap(lambda i: layer(cfg, key, i, True))(jnp.arange(nd, n))
    return {"embed": embeddings(cfg, key),
            "layers": {"attn_mlp": _stack(cfg, dense, nd),
                       "attn_moe": _stack(cfg, moe, n - nd)},
            "final_norm": {"scale": jnp.ones((cfg["hidden_size"],), dtype)}}
