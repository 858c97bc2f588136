"""NTX streaming reductions (SUM / MIN / MAX / ARGMIN / ARGMAX) in Pallas.

The reducing half of the command set: a descriptor whose ``init_level``
covers the streamed axis. The Pallas grid's last dimension walks the
reduction axis in VMEM-sized tiles; the running accumulator (and the index
counter for the arg ops — the paper's comparator + index-counter datapath)
lives in VMEM scratch across grid steps, with a single write-back at the
last step (deferred rounding, as in the PCS datapath).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ntx_elementwise import _apply_op, _OPS2

_INIT = {"sum": 0.0, "min": float("inf"), "max": float("-inf"),
         "argmin": float("inf"), "argmax": float("-inf")}


def _reduce_kernel(x_ref, o_ref, acc_ref, idx_ref, *, op: str, nk: int,
                   block: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _INIT[op])
        if op in ("argmin", "argmax"):
            idx_ref[...] = jnp.zeros_like(idx_ref)

    x = x_ref[...].astype(jnp.float32)          # (rows, block)
    if op == "sum":
        acc_ref[...] += x.sum(-1, keepdims=True)
    elif op == "min":
        acc_ref[...] = jnp.minimum(acc_ref[...], x.min(-1, keepdims=True))
    elif op == "max":
        acc_ref[...] = jnp.maximum(acc_ref[...], x.max(-1, keepdims=True))
    else:
        # comparator + index counter: local arg, then global first-wins merge
        local = (jnp.argmin(x, -1) if op == "argmin"
                 else jnp.argmax(x, -1)).astype(jnp.int32)[:, None]
        val = (x.min(-1, keepdims=True) if op == "argmin"
               else x.max(-1, keepdims=True))
        better = (val < acc_ref[...]) if op == "argmin" else (val > acc_ref[...])
        idx_ref[...] = jnp.where(better, local + k * block, idx_ref[...])
        acc_ref[...] = jnp.where(better, val, acc_ref[...])

    @pl.when(k == nk - 1)
    def _store():
        if op in ("argmin", "argmax"):
            o_ref[...] = idx_ref[...].astype(o_ref.dtype)
        else:
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _chain_reduce_kernel(*refs, stages, n_ys: int, red: str, nk: int,
                         block: int, n_valid: int):
    """Chain stages applied per block, the chain value written back AND
    accumulated into the reduction in the same pass — the paper's streaming
    ops feeding the wide accumulator without a second TCDM trip. The arg
    tails additionally carry the index counter (comparator + index-counter
    datapath); first-wins merging across blocks matches ``np.argmax``."""
    x_ref = refs[0]
    y_refs = refs[1:1 + n_ys]
    o_ref, r_ref = refs[1 + n_ys], refs[2 + n_ys]
    acc_ref, idx_ref = refs[3 + n_ys], refs[4 + n_ys]
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _INIT[red])
        if red in ("argmin", "argmax"):
            idx_ref[...] = jnp.zeros_like(idx_ref)

    val = x_ref[...]
    yi = 0
    for op, imm in stages:
        y = None
        if op in _OPS2:
            y = y_refs[yi][...]
            yi += 1
        val = _apply_op(op, val, y, imm)
    o_ref[...] = val

    # padded columns must contribute the reduction identity
    col = k * block + jax.lax.broadcasted_iota(jnp.int32, val.shape, 1)
    v = jnp.where(col < n_valid, val.astype(jnp.float32), _INIT[red])
    if red == "sum":
        acc_ref[...] += v.sum(-1, keepdims=True)
    elif red == "min":
        acc_ref[...] = jnp.minimum(acc_ref[...], v.min(-1, keepdims=True))
    elif red == "max":
        acc_ref[...] = jnp.maximum(acc_ref[...], v.max(-1, keepdims=True))
    else:
        local = (jnp.argmin(v, -1) if red == "argmin"
                 else jnp.argmax(v, -1)).astype(jnp.int32)[:, None]
        best = (v.min(-1, keepdims=True) if red == "argmin"
                else v.max(-1, keepdims=True))
        better = ((best < acc_ref[...]) if red == "argmin"
                  else (best > acc_ref[...]))
        idx_ref[...] = jnp.where(better, local + k * block, idx_ref[...])
        acc_ref[...] = jnp.where(better, best, acc_ref[...])

    @pl.when(k == nk - 1)
    def _store():
        if red in ("argmin", "argmax"):
            r_ref[...] = idx_ref[...].astype(r_ref.dtype)
        else:
            r_ref[...] = acc_ref[...]


def chain_reduce_pallas(stages, red: str, x: jnp.ndarray, ys: tuple = (),
                        n_valid: int | None = None, block: int = 512,
                        interpret: bool = False):
    """Fused elementwise chain + reduction tail over (rows, n).

    Returns (chain_out (rows, n), reduction (rows, 1)). ``red`` is one of
    sum/min/max/argmin/argmax — the arg tails return the winning index
    (as fp32; ties resolve first-wins like ``np.argmax``); ``n_valid``
    masks padded columns out of the reduction.
    """
    assert red in ("sum", "min", "max", "argmin", "argmax"), red
    stages = tuple((str(op), float(imm)) for op, imm in stages)
    n_ys = sum(1 for op, _ in stages if op in _OPS2)
    assert len(ys) == n_ys, (len(ys), n_ys)
    rows, n = x.shape
    assert n % block == 0, (n, block)
    nk = n // block
    n_valid = n if n_valid is None else n_valid
    spec = pl.BlockSpec((rows, block), lambda r, k: (r, k))
    args = (x,) + tuple(ys)
    return pl.pallas_call(
        functools.partial(_chain_reduce_kernel, stages=stages, n_ys=n_ys,
                          red=red, nk=nk, block=block, n_valid=n_valid),
        grid=(1, nk),
        in_specs=[spec] * len(args),
        out_specs=(spec, pl.BlockSpec((rows, 1), lambda r, k: (r, 0))),
        out_shape=(jax.ShapeDtypeStruct((rows, n), x.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)


def reduce_pallas(op: str, x: jnp.ndarray, block: int = 512,
                  interpret: bool = False) -> jnp.ndarray:
    """Reduce (rows, n) over the last axis -> (rows, 1).

    ``n % block == 0`` required (ops.py pads with the op identity).
    """
    rows, n = x.shape
    assert n % block == 0, (n, block)
    nk = n // block
    out_dtype = jnp.int32 if op in ("argmin", "argmax") else jnp.float32
    out = pl.pallas_call(
        functools.partial(_reduce_kernel, op=op, nk=nk, block=block),
        grid=(1, nk),
        in_specs=[pl.BlockSpec((rows, block), lambda r, k: (r, k))],
        out_specs=pl.BlockSpec((rows, 1), lambda r, k: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), out_dtype),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x)
    return out[:, 0]
