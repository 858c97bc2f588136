"""Absorbed MLA decode attention in one pass over the latent cache.

A decode query of DeepSeek-V2's absorbed attention meets every cached
position twice: once in the scores (the latent query against the latent
keys, plus the rope query against the shared rope key) and once in the
values (the softmax weights against the same latent). As two XLA fusions
each reads the whole cache. This kernel streams each row's cache through
VMEM once, a block of positions at a time, and keeps the NTX online
softmax of ``flash_attention.py``: a running max (MAX reduction), a
running sum and a float32 accumulator of ``p . c`` (MAC reductions).

Its operands are a kind's whole cache stacks; the layer and ``fill`` are
prefetched scalars and the index maps pick the layer, so no slice of the
stack is made. Only blocks that hold a position below ``fill`` are
read: the block index clamps to the last of them, which the pipeline
does not fetch again, and the compute of later blocks is skipped.

The kernel returns the unnormalised accumulator with its running max and
sum; the caller merges this step's own entries under the same softmax.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the ``pallas_call``'s name, which the compiled program and the device
#: trace show
KERNEL_NAME = "mla_decode_onepass"
#: rows x positions a grid step: the fastest of six shapes tried at
#: DeepSeek-V2-Lite's widths (latent 512, 16 heads, b 128, seq 1024) on a
#: TPU v5e; blocks of 128 positions ran ~50% slower
BLOCK_ROWS = 4
BLOCK_K = 512


def blocks(batch: int, seq: int, rank: int,
           rope_dim: int) -> Optional[Tuple[int, int]]:
    """(rows, positions) of a grid step for a ``batch`` x ``seq`` cache of
    latents ``rank`` and rope keys ``rope_dim`` wide, or None where the
    kernel does not take the shape. It reads both stacks where they lie
    only in the layouts the TPU gives them at a latent of whole 128-lane
    rows and a narrower rope key; at other widths each call would copy a
    stack."""
    if seq % BLOCK_K or rank % 128 or rope_dim >= 128:
        return None
    return math.gcd(batch, BLOCK_ROWS), BLOCK_K


def _kernel(layer_ref, fill_ref, q_ref, qr_ref, c_ref, kr_ref,
            acc_ref, m_ref, l_ref, *, scale: float, rows: int,
            block_k: int):
    del layer_ref                       # read by the index maps only
    ik = pl.program_id(1)
    fill = fill_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik * block_k < fill)
    def _step():
        pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        valid = pos < fill
        nt = (((1,), (1,)), ((), ()))
        for r in range(rows):
            c = c_ref[r]                                    # (bk, R)
            s = (jax.lax.dot_general(q_ref[r], c, nt,
                                     preferred_element_type=jnp.float32)
                 + jnp.dot(qr_ref[r], kr_ref[r],
                           preferred_element_type=jnp.float32)
                 ) * scale                                  # (h, bk)
            s = jnp.where(valid, s, -jnp.inf)
            m_prev = m_ref[r]                               # (h, 1)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[r] = corr * l_ref[r] + p.sum(-1, keepdims=True)
            acc_ref[r] = corr * acc_ref[r] + jnp.dot(
                p.astype(c.dtype), c, preferred_element_type=jnp.float32)
            m_ref[r] = m_new


def mla_decode_onepass(q_lat: jnp.ndarray, q_rope: jnp.ndarray,
                       c_kv: jnp.ndarray, k_rope: jnp.ndarray, layer, fill,
                       *, scale: float, interpret: bool = False):
    """One decode query a row over layer ``layer`` of the latent stacks.

    q_lat: (b, h, R) and q_rope: (b, h, dr), in the cache's dtype;
    c_kv: (L, b, seq, R) and k_rope: (L, b, 1, seq, dr), every layer of a
    kind; positions ``< fill`` are attended. The kernel reads k_rope with
    its last two axes swapped, as the TPU lays out a stack whose last axis
    is narrower than a vector register's 128 lanes, so the swap is free.
    Returns float32 (acc (b, h, R), m (b, h, 1), l (b, h, 1)): ``acc /
    l`` is the attention of the cached positions, ``m`` the scores' max
    (-inf where ``fill`` is 0).
    """
    b, h, r = q_lat.shape
    dr = q_rope.shape[-1]
    seq = c_kv.shape[2]
    rows, block_k = blocks(b, seq, r, dr)

    def last(fill_ref):
        return jnp.maximum(fill_ref[0] - 1, 0) // block_k

    def row_map(ib, ik, layer_ref, fill_ref):
        return ib, 0, 0

    def c_map(ib, ik, layer_ref, fill_ref):
        return layer_ref[0], ib, jnp.minimum(ik, last(fill_ref)), 0

    def kr_map(ib, ik, layer_ref, fill_ref):
        return layer_ref[0], ib, 0, 0, jnp.minimum(ik, last(fill_ref))

    out_spec = pl.BlockSpec((rows, h, 1), row_map)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, rows=rows, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // rows, seq // block_k),
            in_specs=[
                pl.BlockSpec((rows, h, r), row_map),
                pl.BlockSpec((rows, h, dr), row_map),
                pl.BlockSpec((None, rows, block_k, r), c_map),
                pl.BlockSpec((None, rows, None, dr, block_k), kr_map),
            ],
            out_specs=[pl.BlockSpec((rows, h, r), row_map), out_spec,
                       out_spec]),
        out_shape=[jax.ShapeDtypeStruct((b, h, r), f32),
                   jax.ShapeDtypeStruct((b, h, 1), f32),
                   jax.ShapeDtypeStruct((b, h, 1), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      jnp.reshape(jnp.asarray(fill, jnp.int32), (1,)),
      q_lat, q_rope, c_kv, jnp.swapaxes(k_rope, -1, -2))
