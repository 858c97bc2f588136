"""AdamW built from the NTX elementwise command set (no optax).

Mixed-precision, ZeRO-friendly layout: the *stored* params may be bf16
(compute copy); the optimizer state carries the fp32 master plus (m, v),
all shardable over (data x model) via distributed.sharding.opt_state_specs.
The update itself is the AXPY/MUL/thresholding bundle the paper's
accelerator was built to stream — on TPU it runs through the fused
``adamw_pallas`` kernel when the Pallas backend is active.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def lr_schedule(cfg: AdamWConfig, step: jnp.ndarray) -> jnp.ndarray:
    """Linear warmup + cosine decay (the standard production schedule)."""
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    t = jnp.clip((step - cfg.warmup_steps)
                 / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def init_opt_state(params: Any) -> dict:
    """master fp32 + first/second moments (+ step counter).

    zeros_like (not zeros) so moments inherit the params' shardings when
    initialised from mesh-distributed parameters. ``master`` is always a
    copy, even of fp32 params, so a train step may donate both trees."""
    master = jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                          params)
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32),
                         params)
    # the step counter is a host scalar (uncommitted) so the state tree
    # never pins mixed device placements under jit
    return {"master": master, "m": zeros,
            "v": jax.tree.map(jnp.copy, zeros),
            "step": np.zeros((), np.int32)}


def global_norm(grads: Any) -> jnp.ndarray:
    leaves = [jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads: Any, max_norm: float) -> Tuple[Any, jnp.ndarray]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads), norm


def apply_updates(cfg: AdamWConfig, params: Any, grads: Any,
                  state: dict, use_fused: bool = False) -> Tuple[Any, dict]:
    """One AdamW step. Returns (new_params_in_storage_dtype, new_state)."""
    step = state["step"] + 1
    lr = lr_schedule(cfg, step)
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    b1, b2, eps, wd = cfg.b1, cfg.b2, cfg.eps, cfg.weight_decay
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p_master, g, m, v):
        if use_fused and p_master.ndim == 2:
            po, mo, vo = ops.adamw_update(p_master, g, m, v, step, lr=lr,
                                          b1=b1, b2=b2, eps=eps, wd=wd)
            return po, mo, vo
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / bc1
        vhat = v / bc2
        p = p_master - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p_master)
        return p, m, v

    flat_master, treedef = jax.tree.flatten(state["master"])
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    out = [upd(pm, g, m, v) for pm, g, m, v
           in zip(flat_master, flat_g, flat_m, flat_v)]
    new_master = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    new_params = jax.tree.map(lambda pm, p: pm.astype(p.dtype),
                              new_master, params)
    return new_params, {"master": new_master, "m": new_m, "v": new_v,
                        "step": step}
