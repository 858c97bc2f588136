"""NTX streaming GEMM as a Pallas TPU kernel.

The mapping from the paper's machine to this kernel is 1:1:

  NTX hardware loops (outer levels)  ->  the Pallas ``grid`` (i, j, k)
  AGU affine addressing              ->  ``BlockSpec.index_map``
  TCDM tiles + DMA double buffering  ->  the memory-hierarchy subsystem:
                                         ``core.memory.NtxMemSpec`` models
                                         the capacity/DMA rates, block
                                         sizes come from the double-buffer
                                         tile scheduler through the
                                         autotune cache (``ops.matmul_
                                         blocks``), and programs whose
                                         working set exceeds TCDM are
                                         rewritten into explicit
                                         DMA-in -> compute -> DMA-out tile
                                         loops by ``core.tiling.TilePlan``
                                         (within one kernel call the
                                         Mosaic grid pipeline stages the
                                         same scheme natively)
  PCS wide accumulator               ->  fp32 VMEM scratch accumulator,
                                         written back (rounded) ONCE at the
                                         last k-step (init_level/store_level
                                         = the k loop, exactly like the
                                         descriptor's init/store levels)

``compensated=True`` additionally carries a Neumaier compensation term
across k-blocks — the closest TPU analogue of the ~300-bit PCS register for
fp32 inputs (bf16 inputs already get exact fp32 MXU accumulation per block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



#: Epilogue stage kinds that carry a streamed array operand (in order).
EPILOGUE_ARRAY_KINDS = ("bias", "residual", "mul", "sub", "mask")
#: All supported epilogue kinds.
EPILOGUE_KINDS = EPILOGUE_ARRAY_KINDS + ("scale", "relu", "thresh",
                                         "silu", "gelu")


def apply_epilogue(acc, stages, operands):
    """Apply fused epilogue stages to the fp32 accumulator.

    ``stages``: static tuple of (kind, imm). ``operands``: one array (or
    ref-loaded block) per array kind, in stage order. Runs inside the
    kernel's store step — the exact point the descriptor's store_level
    rounds and writes back, so the whole epilogue costs zero extra HBM
    round trips.
    """
    i = 0
    for kind, imm in stages:
        if kind == "bias":           # + row vector broadcast over rows
            acc = acc + operands[i].astype(jnp.float32)
            i += 1
        elif kind == "residual":     # + full matrix
            acc = acc + operands[i].astype(jnp.float32)
            i += 1
        elif kind == "mul":          # * full matrix (e.g. a gate)
            acc = acc * operands[i].astype(jnp.float32)
            i += 1
        elif kind == "sub":          # - full matrix (SUB: acc - rd1)
            acc = acc - operands[i].astype(jnp.float32)
            i += 1
        elif kind == "mask":         # MASK: keep acc where rd1 != 0
            acc = jnp.where(operands[i] != 0, acc, jnp.zeros_like(acc))
            i += 1
        elif kind == "scale":
            acc = acc * jnp.float32(imm)
        elif kind == "relu":
            acc = jnp.maximum(acc, 0.0)
        elif kind == "thresh":
            acc = jnp.where(acc > jnp.float32(imm), acc, 0.0)
        elif kind == "silu":
            acc = acc * jax.nn.sigmoid(acc)
        elif kind == "gelu":
            acc = jax.nn.gelu(acc)
        else:
            raise ValueError(kind)
    return acc


def _dot(a, b):
    """fp32 accumulate; fp32 operands multiply at full fp32 precision (the
    MXU's multi-pass mode), like the NTX FMA's fp32 product. Without the
    explicit precision the MXU may round fp32 operands to bf16."""
    precision = (jax.lax.Precision.HIGHEST
                 if jnp.float32 in (a.dtype, b.dtype) else None)
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


def _gemm_kernel(a_ref, b_ref, *rest, nk: int, out_dtype, stages=(),
                 n_ep: int = 0):
    ep_refs = rest[:n_ep]
    c_ref, acc_ref = rest[n_ep], rest[n_ep + 1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():                       # descriptor init_level: fresh pass
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    acc_ref[...] += _dot(a, b)

    @pl.when(k == nk - 1)
    def _store():                      # descriptor store_level: one rounding
        acc = apply_epilogue(acc_ref[...], stages,
                             [r[...] for r in ep_refs])
        c_ref[...] = acc.astype(out_dtype)


def _gemm_kernel_kahan(a_ref, b_ref, *rest, nk: int, out_dtype, stages=(),
                       n_ep: int = 0):
    ep_refs = rest[:n_ep]
    c_ref, acc_ref, comp_ref = rest[n_ep], rest[n_ep + 1], rest[n_ep + 2]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    x = _dot(a_ref[...], b_ref[...])
    acc = acc_ref[...]
    t = acc + x
    comp_ref[...] += jnp.where(jnp.abs(acc) >= jnp.abs(x),
                               (acc - t) + x, (x - t) + acc)
    acc_ref[...] = t

    @pl.when(k == nk - 1)
    def _store():
        acc = apply_epilogue(acc_ref[...] + comp_ref[...], stages,
                             [r[...] for r in ep_refs])
        c_ref[...] = acc.astype(out_dtype)


def gemm_pallas(a: jnp.ndarray, b: jnp.ndarray, *,
                block_m: int = 128, block_n: int = 128, block_k: int = 128,
                out_dtype=jnp.float32, compensated: bool = False,
                epilogue=None,
                interpret: bool = False) -> jnp.ndarray:
    """C[m,n] = epilogue(A[m,k] @ B[k,n]). Dims must divide the block sizes
    (``repro.kernels.ops.gemm`` pads arbitrary shapes).

    ``epilogue``: sequence of (kind, imm, operand) stages applied to the
    fp32 accumulator at the final k-step, before the single rounding write.
    Array operands: ``bias`` takes a (1, n) row vector, ``residual``/``mul``
    take (m, n) matrices.
    """
    m, kdim = a.shape
    k2, n = b.shape
    assert kdim == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and kdim % block_k == 0, (
        (m, n, kdim), (block_m, block_n, block_k))
    nk = kdim // block_k
    grid = (m // block_m, n // block_n, nk)

    epilogue = tuple(epilogue or ())
    stages = tuple((kind, float(imm)) for kind, imm, _ in epilogue)
    ep_args, ep_specs = [], []
    for kind, _, operand in epilogue:
        if kind not in EPILOGUE_ARRAY_KINDS:
            continue
        if kind == "bias":
            assert operand.shape == (1, n), (kind, operand.shape)
            ep_specs.append(pl.BlockSpec((1, block_n),
                                         lambda i, j, k: (0, j)))
        else:
            assert operand.shape == (m, n), (kind, operand.shape)
            ep_specs.append(pl.BlockSpec((block_m, block_n),
                                         lambda i, j, k: (i, j)))
        ep_args.append(operand)

    kern = _gemm_kernel_kahan if compensated else _gemm_kernel
    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
    if compensated:
        scratch.append(pltpu.VMEM((block_m, block_n), jnp.float32))

    return pl.pallas_call(
        functools.partial(kern, nk=nk, out_dtype=out_dtype, stages=stages,
                          n_ep=len(ep_args)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),  # AGU0
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),  # AGU1
            *ep_specs,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),  # AGU2
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, *ep_args)
