"""Mamba-2 SSD (state-space duality) chunked scan as a Pallas kernel.

The SSD blocked algorithm is an NTX generalized reduction at chunk
granularity: the inter-chunk recurrent state S (d_state x d_head) is the
wide accumulator, initialised once per sequence (``init_level`` = the chunk
loop), updated with decay-weighted MACs per chunk, and combined with the
intra-chunk quadratic part. The chunk loop is the sequential grid dimension;
S lives in VMEM scratch across chunk steps, exactly like the GEMM k-loop
accumulator.

Layout: one (batch*head) per grid row; B/C are broadcast per head by the
wrapper (ops.ssd_scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_ref, *,
                chunk: int):
    c_idx = pl.program_id(1)
    h = pl.program_id(0)

    @pl.when(c_idx == 0)
    def _init():                                  # init_level: new sequence
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)              # (L, dh)
    dt = dt_ref[0].astype(jnp.float32)            # (L, 1) column
    B = b_ref[0].astype(jnp.float32)              # (L, n)
    C = c_ref[0].astype(jnp.float32)              # (L, n)
    A = a_ref[h]                                  # scalar decay rate (<0)

    # inclusive log-decay prefix sums, as a column and as a row, from
    # masked sums over the (L, L) broadcast of dt*A: Mosaic keeps 1-D
    # vectors (and cumsum) out of the kernel
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    da = jnp.broadcast_to(dt * A, (chunk, chunk))           # da[i, j] = dA[i]
    la_col = jnp.sum(jnp.where(tri, da.T, 0.0), axis=1,
                     keepdims=True)                         # (L, 1)
    la_row = jnp.sum(jnp.where(row <= col, da, 0.0), axis=0,
                     keepdims=True)                         # (1, L)
    # intra-chunk quadratic part: masked decay-weighted (C.B^T)
    dec = jnp.exp(la_col - la_row)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    w = jnp.where(tri, cb * dec, 0.0)             # (L, L)
    xdt = x * dt                                  # (L, dh)
    y = jax.lax.dot_general(w, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    s = s_ref[...]                                # (n, dh)
    y = y + jnp.exp(la_col) * jax.lax.dot_general(
        C, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # state update (the wide-accumulator MAC): S <- e^{la_L} S + B^T W X
    la_last = la_col[chunk - 1:chunk, :]          # (1, 1)
    wS = jnp.exp(la_last - la_col) * dt           # (L, 1)
    s_ref[...] = jnp.exp(la_last) * s + jax.lax.dot_general(
        (B * wS).T, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                    B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 64,
                    interpret: bool = False) -> jnp.ndarray:
    """x: (bh, l, dh); dt: (bh, l); A: (bh,); B/C: (bh, l, n). l % chunk == 0."""
    bh, l, dh = x.shape
    n = B.shape[-1]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # A
            pl.BlockSpec((1, chunk, dh), lambda h, c: (h, c, 0)),    # x
            pl.BlockSpec((1, chunk, 1), lambda h, c: (h, c, 0)),     # dt
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),     # B
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),     # C
        ],
        out_specs=pl.BlockSpec((1, chunk, dh), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, l, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), x, dt[..., None], B, C)
