"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Maps pipeline stages onto an axis (typically ``pod`` in the multi-pod mesh:
stage s on pod s). Microbatches stream through stages with the classic
(n_micro + n_stages - 1)-step schedule; activations hop stages via
``collective_permute`` so XLA can overlap the hop with the next
microbatch's compute — the cluster-to-cluster analogue of the paper's
double-buffered DMA.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def gpipe(body: Callable, axis_name: str):
    """Build a pipelined apply: ``fn(stage_params, x_micro) -> y_micro``.

    Returns ``run(params_local, xs)`` for use under shard_map, where
    ``params_local`` is this stage's parameter shard (params stacked over
    stages outside) and ``xs`` is (n_micro, mb, ...) microbatched input
    held by stage 0. Output: (n_micro, mb, ...) at the last stage
    (other stages return zeros).
    """

    def run(params_local, xs):
        n_stage = jax.lax.psum(1, axis_name)
        idx = jax.lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        mb_shape = xs.shape[1:]
        perm = [(i, i + 1) for i in range(n_stage - 1)]

        total = n_micro + n_stage - 1
        ys = jax.lax.pcast(jnp.zeros_like(xs), axis_name, to="varying")

        def step(t, carry):
            cur, ys = carry                      # cur: activation entering
            #                                      this stage at step t
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            x_in = jnp.where(idx == 0, feed, cur)
            active = (t - idx >= 0) & (t - idx < n_micro)
            y = body(params_local, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage collects its finished microbatch
            out_slot = jnp.clip(t - (n_stage - 1), 0, n_micro - 1)
            take = active & (idx == n_stage - 1)
            upd = jnp.where(take, y,
                            jax.lax.dynamic_index_in_dim(ys, out_slot, 0,
                                                         keepdims=False))
            ys = jax.lax.dynamic_update_index_in_dim(ys, upd, out_slot, 0)
            # hop to the next stage
            cur = jax.lax.ppermute(y, axis_name, perm) if n_stage > 1 else y
            return cur, ys

        cur = jax.lax.pcast(jnp.zeros(mb_shape, xs.dtype), axis_name,
                            to="varying")
        cur, ys = jax.lax.fori_loop(0, total, step, (cur, ys))
        # results live on the last stage only; broadcast to all stages
        return jax.lax.psum(ys, axis_name)

    return run


def pipelined_apply(mesh: Mesh, body: Callable, stage_axis: str,
                    params_specs, x_spec, y_spec):
    """Wrap ``gpipe`` in shard_map over ``stage_axis`` of ``mesh``."""
    run = gpipe(body, stage_axis)
    return jax.shard_map(run, mesh=mesh, in_specs=(params_specs, x_spec),
                         out_specs=y_spec, check_vma=False)
