"""Random weights of a dense decoder LM, made from a seed by the benchmark.

The served model is handed these weights, and the plain reference makes
them again from the same seed, layer by layer, so it takes nothing that
the program made. Every weight is a normal draw from its own key:
``fold_in(fold_in(key, crc32(name)), layer)``, scaled by 1/sqrt(fan-in)
(the embedding table by 1, the query, key and value biases by
``BIAS_STD``), and cast to the served dtype; norm scales are 1. The tree follows the layout the serving program stores: one stack of
``n_layers`` per weight under ``layers/attn_mlp``, the embedding table
and the unembedding padded to a multiple of 256 rows/columns.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp

KIND = "attn_mlp"
#: the program pads its vocabulary to a multiple of this
VOCAB_PAD = 256
#: spread of the query, key and value biases: a tenth of the projections
#: they are added to, so that a bias left out moves the logits while the
#: attention still follows the inputs
BIAS_STD = 0.1


def padded_vocab(cfg: Dict) -> int:
    v = cfg["vocab_size"]
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def head_dim(cfg: Dict) -> int:
    """Qwen2 derives the head size from the hidden size."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_shapes(cfg: Dict) -> Dict[str, tuple]:
    """The matrices of one layer."""
    d, ff, hd = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"mixer/wq": (d, hq * hd), "mixer/wk": (d, hkv * hd),
            "mixer/wv": (d, hkv * hd), "mixer/wo": (hq * hd, d),
            "ffn/w1": (d, ff), "ffn/w2": (ff, d), "ffn/w3": (d, ff)}


def bias_shapes(cfg: Dict) -> Dict[str, tuple]:
    """The query, key and value biases of one layer."""
    hd = head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"mixer/bq": (hq * hd,), "mixer/bk": (hkv * hd,),
            "mixer/bv": (hkv * hd,)}


def _draw(key, name: str, shape, std: float, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def layer(cfg: Dict, key, index) -> Dict[str, jnp.ndarray]:
    """Weights of one layer, flat ``{"mixer/wq": ...}`` (no norms)."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    k = jax.random.fold_in(key, index)
    out = {name: _draw(k, name, shape, shape[0] ** -0.5, dtype)
           for name, shape in layer_shapes(cfg).items()}
    out.update({name: _draw(k, name, shape, BIAS_STD, dtype)
                for name, shape in bias_shapes(cfg).items()})
    return out


def embeddings(cfg: Dict, key) -> Dict[str, jnp.ndarray]:
    dtype = jnp.dtype(cfg["torch_dtype"])
    d, vp = cfg["hidden_size"], padded_vocab(cfg)
    return {"embed": _draw(key, "embed", (vp, d), 1.0, dtype),
            "unembed": _draw(key, "unembed", (d, vp), d ** -0.5, dtype)}


def params(cfg: Dict, key) -> Dict:
    """The whole tree, in the program's layout (call under ``jax.jit``)."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    flat = jax.vmap(lambda i: layer(cfg, key, i))(jnp.arange(n))
    stack: Dict = {"norm1": {"scale": jnp.ones((n, d), dtype)},
                   "norm2": {"scale": jnp.ones((n, d), dtype)}}
    for name, arr in flat.items():
        group, leaf = name.split("/")
        stack.setdefault(group, {})[leaf] = arr
    return {"embed": embeddings(cfg, key), "layers": {KIND: stack},
            "final_norm": {"scale": jnp.ones((d,), dtype)}}
