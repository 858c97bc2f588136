"""Flash attention as an NTX-style streaming reduction.

Online softmax is literally the paper's generalized-reduction pattern: a
MAX reduction (running row max, the comparator datapath), a MAC reduction
(running exp-weighted sums, the FMAC datapath), an accumulator initialised
at the start of the key stream (``init_level`` = the kv loop) and written
back once at its end (``store_level``, deferred rounding). The kv loop is
the last (sequential) grid dimension; running (m, l, acc) state lives in
VMEM scratch; Pallas pipelines the K/V tile DMAs — the paper's
double-buffered TCDM scheme.

Handles self-attention (training/prefill, causal) and decode (sq << skv,
query positioned at ``kv_len - sq + i``) with GQA head mapping in the
index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _flash_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)
    kv_len = lens_ref[0]                        # valid kv entries

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    iq = pl.program_id(1)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    mask = kpos < kv_len
    if causal:
        qpos = (kv_len - (pl.num_programs(1) * block_q)
                + iq * block_q
                + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0))
        mask = mask & (kpos <= qpos)
    logits = jnp.where(mask, logits, _NEG_INF)

    m_prev = m_ref[...]                          # (bq, 1)
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + p.sum(-1, keepdims=True)
    acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _store():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)          # fully-masked row guard
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, scale: float | None = None,
                           kv_len: int | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (b, hq, sq, d); k/v: (b, hkv, skv, d); GQA via hq % hkv == 0.

    ``kv_len``: number of valid kv positions (decode cache fill); defaults
    to skv. Query i is at absolute position kv_len - sq + i (so training
    with sq == skv gives standard causal attention).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    kv_len = skv if kv_len is None else kv_len
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv)
    nq, nk = sq // block_q, skv // block_k

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    lens = jnp.full((1,), kv_len, jnp.int32)

    def kv_index(h, iq, ik):
        return (h // g, ik, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(b, hq, sq, d)
