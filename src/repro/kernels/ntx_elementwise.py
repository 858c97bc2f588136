"""NTX streaming element commands as one Pallas kernel.

Implements the non-reducing half of the paper's command set (Fig. 3b):
AXPY / ADD / SUB / MUL / RELU / THRESH / MASK / COPY / SET — a descriptor
with ``init_level = store_level = 0``: one element out per element in, so
the Pallas grid is a flat stream of VMEM tiles (the TCDM double-buffer).

Also provides the fused AdamW parameter update — the training-side use of
the same machinery (an optimizer step IS an AXPY-family reduction bundle,
which is how the original NTX paper accelerates training).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_OPS1 = {"relu", "thresh", "copy", "set"}
_OPS2 = {"axpy", "add", "sub", "mul", "mask"}


def _apply_op(op: str, x, y, imm: float):
    """One streaming command applied to in-register values."""
    imm = jnp.asarray(imm, x.dtype)
    if op == "axpy":
        return imm * x + y
    if op == "add":
        return x + y
    if op == "sub":
        return x - y
    if op == "mul":
        return x * y
    if op == "mask":
        return jnp.where(y != 0, x, jnp.zeros_like(x))
    if op == "relu":
        return jnp.maximum(x, 0)
    if op == "thresh":
        return jnp.where(x > imm, x, jnp.zeros_like(x))
    if op == "copy":
        return x
    if op == "set":
        return jnp.full_like(x, imm)
    raise ValueError(op)


def _ew_kernel(*refs, op: str, imm: float):
    if op in _OPS2:
        x_ref, y_ref, o_ref = refs
        x, y = x_ref[...], y_ref[...]
    else:
        x_ref, o_ref = refs
        x, y = x_ref[...], None
    o_ref[...] = _apply_op(op, x, y, imm)


def elementwise_pallas(op: str, x: jnp.ndarray, y: jnp.ndarray | None = None,
                       imm: float = 0.0, block: int = 1024,
                       interpret: bool = False) -> jnp.ndarray:
    """Apply one streaming command over a 2-D (rows, n) array.

    ``repro.kernels.ops`` reshapes/pads arbitrary arrays into this layout
    (rows % 8 == 0, n % 128 == 0 for TPU tiling; block divides n).
    """
    rows, n = x.shape
    assert n % block == 0, (n, block)
    grid = (n // block,)
    spec = pl.BlockSpec((rows, block), lambda i: (0, i))
    args = (x,) if op in _OPS1 else (x, y)
    in_specs = [spec] * len(args)
    return pl.pallas_call(
        functools.partial(_ew_kernel, op=op, imm=imm),
        grid=grid,
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)


# ----------------------------------------------------------------------
# Chain compiler: a fused sequence of streaming commands in ONE pass
# ----------------------------------------------------------------------
def _chain_kernel(*refs, stages, n_ys: int):
    """refs: (x_ref, y_ref_0..y_ref_{n_ys-1}, o_ref). ``stages`` is a static
    tuple of (op, imm); 2-read stages consume the next y_ref in order. The
    carried value stays in registers between stages — the VMEM-resident
    analogue of the paper's TCDM-resident operand chain (§II-E)."""
    x_ref = refs[0]
    y_refs = refs[1:1 + n_ys]
    o_ref = refs[1 + n_ys]
    val = x_ref[...]
    yi = 0
    for op, imm in stages:
        y = None
        if op in _OPS2:
            y = y_refs[yi][...]
            yi += 1
        val = _apply_op(op, val, y, imm)
    o_ref[...] = val


def elementwise_chain_pallas(stages, x: jnp.ndarray,
                             ys: tuple = (), block: int = 1024,
                             interpret: bool = False,
                             double_buffer: bool = False) -> jnp.ndarray:
    """Fused chain over a 2-D (rows, n) array: one read of ``x``, one read
    per external operand, one write — no intermediate HBM round trips.

    ``stages``: sequence of (op, imm); ops from the NTX streaming command
    set. ``ys``: one (rows, n) array per 2-read stage, in stage order.

    ``double_buffer=True`` marks the grid ``arbitrary`` (sequential), so
    the Mosaic pipeline stages block i+1's HBM->VMEM copies under block
    i's compute — the native analogue of the TCDM double buffering that
    ``core.tiling.TilePlan`` emulates on the host, with ``block`` sized
    from the memory model (``NtxMemSpec.pallas_block_elems``).
    """
    stages = tuple((str(op), float(imm)) for op, imm in stages)
    n_ys = sum(1 for op, _ in stages if op in _OPS2)
    assert len(ys) == n_ys, (len(ys), n_ys)
    rows, n = x.shape
    assert n % block == 0, (n, block)
    spec = pl.BlockSpec((rows, block), lambda i: (0, i))
    args = (x,) + tuple(ys)
    return pl.pallas_call(
        functools.partial(_chain_kernel, stages=stages, n_ys=n_ys),
        grid=(n // block,),
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                ("arbitrary",) if double_buffer else ("parallel",))),
        interpret=interpret,
    )(*args)


# ----------------------------------------------------------------------
# Fused AdamW step — the training workload the accelerator was built for
# ----------------------------------------------------------------------
def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, bc_ref,
                  po_ref, mo_ref, vo_ref, *, b1, b2, eps, wd, lr):
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1 - b1) * g
    v = b2 * v_ref[...] + (1 - b2) * g * g
    # bc_ref holds (1/(1-b1^t), 1/(1-b2^t)) broadcast scalars in SMEM
    mhat = m * bc_ref[0]
    vhat = v * bc_ref[1]
    p = p_ref[...].astype(jnp.float32)
    p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
    po_ref[...] = p.astype(po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


def adamw_pallas(p, g, m, v, step, *, lr, b1=0.9, b2=0.999, eps=1e-8,
                 wd=0.01, block: int = 1024, interpret: bool = False):
    """Fused AdamW over a 2-D (rows, n) parameter tile. Returns (p, m, v)."""
    rows, n = p.shape
    assert n % block == 0
    bc = jnp.stack([1.0 / (1.0 - b1 ** step), 1.0 / (1.0 - b2 ** step)])
    spec = pl.BlockSpec((rows, block), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd, lr=lr),
        grid=(n // block,),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=(spec, spec, spec),
        out_shape=(jax.ShapeDtypeStruct((rows, n), p.dtype),
                   jax.ShapeDtypeStruct((rows, n), jnp.float32),
                   jax.ShapeDtypeStruct((rows, n), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(p, g, m.astype(jnp.float32), v.astype(jnp.float32), bc)
