"""Compute/communication overlap primitives (beyond-paper optimization).

``ring_allgather_matmul``: the TP/SP boundary matmul ``all_gather(x) @ W``
restructured as a ring — each step multiplies the sequence chunk currently
held while ``collective_permute``-ing the next chunk in, so the ICI transfer
hides behind the MXU. This is the TPU analogue of the paper's §II-E
double-buffered DMA: communication of tile i+1 overlaps compute of tile i,
with the VMEM accumulator playing the PCS register.

Written for use under ``shard_map``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



def ring_allgather_matmul(x: jnp.ndarray, w: jnp.ndarray,
                          axis_name: str) -> jnp.ndarray:
    """x: (s_local, d) sequence-sharded; w: (d, f_local) column-sharded.
    Returns (s_global, f_local) = all_gather(x, seq) @ w, ring-overlapped.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = x.shape[0]
    out = jax.lax.pcast(jnp.zeros((n * s_local, w.shape[1]), jnp.float32),
                        axis_name, to="varying")
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        x_cur, out = carry
        # chunk currently held started at device (idx - i) mod n
        src = (idx - i) % n
        y = jnp.dot(x_cur, w, preferred_element_type=jnp.float32)
        out = jax.lax.dynamic_update_slice(out, y, (src * s_local, 0))
        x_nxt = jax.lax.ppermute(x_cur, axis_name, perm)
        return (x_nxt, out)

    (_, out) = jax.lax.fori_loop(0, n, body, (x, out))
    return out.astype(x.dtype)


def ring_matmul_reducescatter(x: jnp.ndarray, w: jnp.ndarray,
                              axis_name: str) -> jnp.ndarray:
    """x: (s_global, d_local); w: (d_local, f). Computes the row-parallel
    product followed by a reduce-scatter over the sequence axis, as a ring
    that overlaps the partial-sum permute with the next chunk's matmul.
    Returns (s_global/n, f) — this device's sequence shard of x @ w (psum'd
    over ``axis_name``).
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = x.shape[0] // n
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = jax.lax.pcast(jnp.zeros((s_local, w.shape[1]), jnp.float32),
                        axis_name, to="varying")

    def body(i, acc):
        # shift the partial sum in from the previous device (zeros at i=0),
        # then add this device's contribution to the chunk it now holds;
        # chunk (idx - i - 1) mod n finishes at device idx at the last step.
        acc = jax.lax.ppermute(acc, axis_name, perm)
        src = (idx - i - 1) % n
        xc = jax.lax.dynamic_slice(x, (src * s_local, 0),
                                   (s_local, x.shape[1]))
        return acc + jnp.dot(xc, w, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, n, body, acc)
    return acc.astype(x.dtype)
