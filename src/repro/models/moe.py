"""Mixture-of-experts FFN: dropless, over the routed experts held here.

Routing keeps the published width: every token scores all ``n_experts``
(softmax in float32) and takes its ``top_k`` greedily; the gates are
renormalised to sum to one only where ``cfg.norm_topk_prob``.

This chip holds routed experts ``[expert_offset, expert_offset +
n_held_experts)`` (by default all of them) and computes their part of the
result, as one member of an expert-parallel group does before the
exchange; assignments to experts held elsewhere add nothing here. Shared
experts are computed once, for every token.

Nothing is dropped. The ``b*s*top_k`` assignments of the call are grouped
by held expert across the whole batch (one stable sort), and each expert
matrix is one grouped GEMM (``jax.lax.ragged_dot``) over the held
experts' rows. Shapes stay static: the row buffer holds the most that
could be routed here, ``b*s*min(top_k, n_held_experts)``, and the GEMM's
work follows the rows its groups hold.

Named scopes: ``moe.route`` (router, top-k, grouping), ``moe.experts``
(the grouped GEMMs), ``moe.combine`` (gate-weighted sum per token, shared
experts).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .common import ArchConfig, Params, dense_init


def moe_params(cfg: ArchConfig, key) -> Params:
    d, ffe = cfg.d_model, cfg.d_ff_expert
    e = cfg.n_held_experts
    ks = jax.random.split(key, 5)
    p = {"router": dense_init(ks[0], (d, cfg.n_experts), 0, cfg.pdtype),
         "w1": dense_init(ks[1], (e, d, ffe), 1, cfg.pdtype),
         "w2": dense_init(ks[2], (e, ffe, d), 1, cfg.pdtype),
         "w3": dense_init(ks[3], (e, d, ffe), 1, cfg.pdtype)}
    if cfg.n_shared_experts:
        ff_sh = ffe * cfg.n_shared_experts
        km = jax.random.split(ks[4], 3)
        p["shared"] = {"w1": dense_init(km[0], (d, ff_sh), 0, cfg.pdtype),
                       "w2": dense_init(km[1], (ff_sh, d), 0, cfg.pdtype),
                       "w3": dense_init(km[2], (d, ff_sh), 0, cfg.pdtype)}
    return p


def _gmm(rows: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray
         ) -> jnp.ndarray:
    """Grouped GEMM: rows ``[sum(sizes[:g]), sum(sizes[:g+1]))`` times
    ``w[g]``; rows past ``sum(sizes)`` are not computed."""
    return jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes)


def apply_moe(cfg: ArchConfig, p: Params, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (b, s, d) -> (y, aux_loss, load); ``load`` (int32, one per held
    expert) counts the assignments each received."""
    dt = cfg.cdtype
    b, s, d = x.shape
    e, k, held = cfg.n_experts, cfg.top_k, cfg.n_held_experts
    n = b * s * k
    x = x.astype(dt)

    with jax.named_scope("moe.route"):
        logits = (x @ p["router"].astype(dt)).astype(jnp.float32)  # (b,s,e)
        probs = jax.nn.softmax(logits, -1)
        gate, expert = jax.lax.top_k(probs, k)                     # (b,s,k)
        if cfg.norm_topk_prob:
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        # load-balance aux loss (Switch-style): e * sum_e f_e * p_e
        me = probs.mean(1)                                          # (b,e)
        ce = jax.nn.one_hot(expert[..., 0], e, dtype=jnp.float32).mean(1)
        aux = (me * ce).sum(-1).mean() * e

        # group the assignments by held expert, the others last
        local = expert.reshape(n) - cfg.expert_offset
        is_held = (local >= 0) & (local < held)
        group = jnp.where(is_held, local, held)
        order = jnp.argsort(group, stable=True)          # row -> assignment
        bounds = jnp.searchsorted(group[order], jnp.arange(held + 1),
                                  side="left").astype(jnp.int32)
        load = jnp.diff(bounds)                           # (held,)
        row = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32), unique_indices=True)
        cap = b * s * min(k, held)
        src = order[:cap] // k                            # row -> token

    with jax.named_scope("moe.experts"):
        xs = jnp.take(x.reshape(b * s, d), src, axis=0)   # (cap, d)
        h = jax.nn.silu(_gmm(xs, p["w1"], load)) * _gmm(xs, p["w3"], load)
        ys = _gmm(h, p["w2"], load)                       # (cap, d)

    with jax.named_scope("moe.combine"):
        at = jnp.where(is_held, row, 0)
        yt = jnp.where(is_held[:, None], jnp.take(ys, at, axis=0), 0)
        w = gate.reshape(n, 1)
        y = (yt.astype(jnp.float32) * w).reshape(b, s, k, d).sum(2)
        y = y.astype(dt)
        if cfg.n_shared_experts:
            sh = p["shared"]
            hs = (jax.nn.silu(x @ sh["w1"].astype(dt))
                  * (x @ sh["w3"].astype(dt)))
            y = y + hs @ sh["w2"].astype(dt)
    return y, aux.astype(jnp.float32), load
