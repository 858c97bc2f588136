"""Public jit'd wrappers around the NTX Pallas kernels.

Backend selection (process-wide):
  * ``"ref"``              pure-jnp oracles (default — also what the models
                           use for the CPU 512-device dry-run, where Mosaic
                           TPU kernels cannot lower)
  * ``"pallas_interpret"`` Pallas kernels, interpret mode (CPU validation)
  * ``"pallas"``           Pallas kernels, compiled (real TPU)

Wrappers own all padding/reshaping so kernels can assume aligned shapes.
"""
from __future__ import annotations

import contextlib
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax._src.pallas.mosaic.error_handling import MosaicError
from jax._src.pallas.mosaic.lowering import LoweringException

from . import ref
from .ntx_gemm import EPILOGUE_ARRAY_KINDS, gemm_pallas
from .ntx_elementwise import (_OPS2, adamw_pallas, elementwise_chain_pallas,
                              elementwise_pallas)
from .ntx_reduce import chain_reduce_pallas, reduce_pallas
from .ntx_conv import conv2d_pallas
from .flash_attention import flash_attention_pallas
from .ssd_scan import ssd_scan_pallas

_BACKEND = "ref"
_VALID = ("ref", "pallas_interpret", "pallas")


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


@contextlib.contextmanager
def backend(name: str):
    prev = get_backend()
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


def _pallas() -> bool:
    return _BACKEND != "ref"


def _interp() -> bool:
    return _BACKEND == "pallas_interpret"


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0.0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


# ----------------------------------------------------------------------
# GEMM block autotuning: scheduler-derived sizes, cached per shape.
# Mode "measure" (set via set_autotune_mode / ExecutionPolicy.autotune;
# the NTX_AUTOTUNE env var remains as a deprecated fallback) additionally
# times 2-3 candidate triples on first sight of a shape (real-TPU
# measure-and-pick); the scheduler model is the default and the fallback.
# ----------------------------------------------------------------------
_BLOCK_CACHE: dict = {}
_BLOCK_CACHE_STATS = {"hits": 0, "misses": 0, "measured": 0, "refused": 0}

#: what the chip's compilers raise for a candidate they refuse: Pallas's
#: Mosaic lowering (NotImplementedError for a primitive it cannot lower,
#: ValueError for a block off the 8 x 128 tiling, LoweringException with
#: verbose Pallas errors on, MosaicError from Mosaic's own compile) and
#: XLA's compile step (JaxRuntimeError, e.g. a kernel that asks for more
#: VMEM than there is). Caught only around lowering and compiling
#: (``compile_or_refusal``), never around a run.
LOWERING_ERRORS = (NotImplementedError, ValueError, LoweringException,
                   MosaicError, jax.errors.JaxRuntimeError)

_AUTOTUNE_MODES = ("model", "measure")
_AUTOTUNE_OVERRIDE: str | None = None


def _align_up(x: int, mult: int) -> int:
    return max(mult, -(-x // mult) * mult)


def set_autotune_mode(mode: str | None) -> None:
    """Set the process-wide autotune mode (``ExecutionPolicy.autotune``
    drives this per run). ``None`` falls back to the deprecated
    ``NTX_AUTOTUNE`` env var, then the ``model`` default."""
    global _AUTOTUNE_OVERRIDE
    if mode is not None and mode not in _AUTOTUNE_MODES:
        raise ValueError(f"autotune mode must be one of {_AUTOTUNE_MODES}")
    _AUTOTUNE_OVERRIDE = mode


def get_autotune_mode() -> str:
    return _AUTOTUNE_OVERRIDE or os.environ.get("NTX_AUTOTUNE", "model")


@contextlib.contextmanager
def autotune_mode(mode: str):
    """Scoped autotune mode — what ``Executor`` wraps a run in."""
    prev = _AUTOTUNE_OVERRIDE
    set_autotune_mode(mode)
    try:
        yield
    finally:
        set_autotune_mode(prev)


def _autotune_mode() -> str:
    return get_autotune_mode()


def _autotune_measure() -> bool:
    return _autotune_mode() == "measure"


def _candidate_blocks(m: int, n: int, k: int, base) -> list:
    """The model's pick plus nearby triples worth racing (smaller k-slab;
    smaller m-panel), clipped to the padded problem and deduplicated."""
    bm, bn, bk = base
    cands = [base, (bm, bn, max(128, bk // 2)), (max(8, bm // 2), bn, bk)]
    out, seen = [], set()
    for c in cands:
        c = (min(c[0], _align_up(m, 8)), min(c[1], _align_up(n, 128)),
             min(c[2], _align_up(k, 128)))
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def compile_or_refusal(fn, *args):
    """``(compiled fn, None)`` for ``args``, or ``(None, error)`` when the
    compiler refuses it (``LOWERING_ERRORS`` from lowering or compiling).
    Tracing runs outside the catch, so an error in the Python code itself
    propagates, as does anything the compiled function raises when run."""
    traced = jax.jit(fn).trace(*args)
    try:
        return traced.lower().compile(), None
    except LOWERING_ERRORS as e:
        return None, e


def _measure_pick(m: int, n: int, k: int, base) -> tuple[int, int, int]:
    """Race the candidate triples on a representative GEMM and keep the
    fastest (first sight of a shape only — the result is cached). A
    candidate the compiler refuses is counted (``refused`` in
    ``block_cache_stats``); if every candidate is refused, the last
    refusal is raised."""
    a = jnp.ones((m, k), jnp.float32)
    b = jnp.ones((k, n), jnp.float32)
    best, best_t, last_err = base, float("inf"), None
    for cand in _candidate_blocks(m, n, k, base):
        bm, bn, bk = cand
        a2, _ = _pad_to(a, 0, bm)
        a2, _ = _pad_to(a2, 1, bk)
        b2, _ = _pad_to(b, 0, bk)
        b2, _ = _pad_to(b2, 1, bn)
        run, err = compile_or_refusal(
            functools.partial(gemm_pallas, block_m=bm, block_n=bn,
                              block_k=bk, interpret=_interp()), a2, b2)
        if err is not None:
            _BLOCK_CACHE_STATS["refused"] += 1
            last_err = err
            continue
        jax.block_until_ready(run(a2, b2))     # warm
        t0 = time.perf_counter()
        jax.block_until_ready(run(a2, b2))
        dt = time.perf_counter() - t0
        if dt < best_t:
            best, best_t = cand, dt
    if best_t == float("inf"):
        raise last_err
    return best


def matmul_blocks(m: int, n: int, k: int,
                  dtype_bytes: int = 4) -> tuple[int, int, int]:
    """(bm, bn, bk) for an (m, n, k) matmul, from the double-buffer tile
    scheduler's VMEM sizing (``scheduler.pick_matmul_blocks``), aligned to
    the TPU tiling the kernels assume (sublane 8 / lane 128) and cached
    per shape — the autotune cache. Wrappers pad operands up to the block
    multiples, so alignment never exceeds the old padding behaviour.
    In autotune mode ``measure`` (``set_autotune_mode`` /
    ``ExecutionPolicy.autotune``; the ``NTX_AUTOTUNE`` env var is the
    deprecated fallback) with a Pallas backend active, the first sight of
    a shape races candidate triples and caches the winner.

    The memo key includes the active backend and autotune mode in
    addition to the shape and ``dtype_bytes``: a cache warmed under
    ``ref``/``model`` must NOT be served verbatim after switching to
    ``measure``/Pallas (that would silently skip measured racing), and a
    measured pick is only valid for the backend it was raced on."""
    key = (m, n, k, dtype_bytes, _BACKEND, _autotune_mode())
    hit = _BLOCK_CACHE.get(key)
    if hit is not None:
        _BLOCK_CACHE_STATS["hits"] += 1
        return hit
    _BLOCK_CACHE_STATS["misses"] += 1
    from repro.core.scheduler import pick_matmul_blocks
    bm, bn, bk = pick_matmul_blocks(m, n, k, dtype_bytes=dtype_bytes)
    blocks = (_align_up(bm, 8), _align_up(bn, 128), _align_up(bk, 128))
    if _autotune_measure() and _pallas():
        blocks = _measure_pick(m, n, k, blocks)
        _BLOCK_CACHE_STATS["measured"] += 1
    _BLOCK_CACHE[key] = blocks
    return blocks


def block_cache_stats() -> dict:
    return dict(_BLOCK_CACHE_STATS)


def clear_autotune_cache() -> None:
    """Drop every memoized block pick and reset the hit/miss counters.

    Call after changing the execution environment in ways the memo key
    cannot see (e.g. moving the process to different hardware)."""
    _BLOCK_CACHE.clear()
    for k in _BLOCK_CACHE_STATS:
        _BLOCK_CACHE_STATS[k] = 0


def _norm_epilogue(epilogue):
    """Normalize user stages to (kind, imm, operand) triples."""
    out = []
    for stage in epilogue or ():
        if isinstance(stage, str):
            stage = (stage,)
        kind = stage[0]
        if kind in EPILOGUE_ARRAY_KINDS:
            operand = stage[1]
            out.append((kind, 0.0, jnp.asarray(operand)))
        elif kind in ("scale", "thresh"):
            out.append((kind, float(stage[1]), None))
        else:
            out.append((kind, 0.0, None))
    return out


def _ref_epilogue(c: jnp.ndarray, epilogue) -> jnp.ndarray:
    """Oracle for the fused epilogue: fp32, same stage order."""
    c = c.astype(jnp.float32)
    for kind, imm, operand in epilogue:
        if kind == "bias":
            c = c + operand.reshape(1, -1).astype(jnp.float32)
        elif kind == "residual":
            c = c + operand.astype(jnp.float32)
        elif kind == "mul":
            c = c * operand.astype(jnp.float32)
        elif kind == "sub":
            c = c - operand.astype(jnp.float32)
        elif kind == "mask":
            c = jnp.where(operand != 0, c, jnp.zeros_like(c))
        elif kind == "scale":
            c = c * jnp.float32(imm)
        elif kind == "relu":
            c = jnp.maximum(c, 0.0)
        elif kind == "thresh":
            c = jnp.where(c > jnp.float32(imm), c, 0.0)
        elif kind == "silu":
            c = jax.nn.silu(c)
        elif kind == "gelu":
            c = jax.nn.gelu(c)
        else:
            raise ValueError(kind)
    return c


# ----------------------------------------------------------------------
# GEMM
# ----------------------------------------------------------------------
def gemm(a: jnp.ndarray, b: jnp.ndarray, out_dtype=jnp.float32,
         compensated: bool = False, epilogue=None) -> jnp.ndarray:
    """C = epilogue(A @ B), fp32 accumulate, arbitrary shapes.

    ``epilogue``: optional fused stages applied to the accumulator at the
    store step (one rounding, zero extra HBM round trips): ("bias", vec),
    ("residual", mat), ("mul", mat), ("scale", s), ("thresh", t), "relu",
    "silu", "gelu".
    """
    epilogue = _norm_epilogue(epilogue)
    if not _pallas():
        c = ref.gemm(a, b, jnp.float32)
        return _ref_epilogue(c, epilogue).astype(out_dtype)
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = matmul_blocks(m, n, k)
    bm, bn, bk = min(bm, _align_up(m, 8)), min(bn, _align_up(n, 128)), \
        min(bk, _align_up(k, 128))
    a2, m0 = _pad_to(a, 0, bm)
    a2, k0 = _pad_to(a2, 1, bk)
    b2, _ = _pad_to(b, 0, bk)
    b2, n0 = _pad_to(b2, 1, bn)
    ep = []
    for kind, imm, operand in epilogue:
        if kind == "bias":
            op2, _ = _pad_to(operand.reshape(1, -1), 1, bn)
        elif kind in EPILOGUE_ARRAY_KINDS:
            op2, _ = _pad_to(operand, 0, bm)
            op2, _ = _pad_to(op2, 1, bn)
        else:
            op2 = None
        ep.append((kind, imm, op2))
    c = gemm_pallas(a2, b2, block_m=bm, block_n=bn, block_k=bk,
                    out_dtype=out_dtype, compensated=compensated,
                    epilogue=ep, interpret=_interp())
    return c[:m0, :n0]


# ----------------------------------------------------------------------
# Fused transformer MLP: activations/gate/residual as GEMM epilogues
# ----------------------------------------------------------------------
def fused_mlp(x: jnp.ndarray, w1: jnp.ndarray, w2: jnp.ndarray,
              w3: jnp.ndarray | None = None, act: str = "gelu",
              residual: jnp.ndarray | None = None) -> jnp.ndarray:
    """``(residual +) (act(x @ w1) [* (x @ w3)]) @ w2`` for (..., d) inputs.

    On the Pallas backends the activation, SwiGLU gate multiply, and the
    residual add all run inside the GEMM store steps (fused epilogues); on
    the ref backend the math is the plain-jnp form the models used before,
    bit-for-bit.
    """
    if not _pallas():
        if act == "swiglu":
            h = jax.nn.silu(x @ w1) * (x @ w3)
        else:
            h = jax.nn.gelu(x @ w1)
        out = h @ w2
        return out if residual is None else residual + out
    dt = x.dtype
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if act == "swiglu":
        gate = gemm(x2, w3, out_dtype=jnp.float32)
        h = gemm(x2, w1, out_dtype=dt, epilogue=[("silu",), ("mul", gate)])
    else:
        h = gemm(x2, w1, out_dtype=dt, epilogue=[("gelu",)])
    ep = []
    if residual is not None:
        ep.append(("residual", residual.reshape(-1, w2.shape[-1])))
    out = gemm(h, w2, out_dtype=dt, epilogue=ep)
    return out.reshape(*lead, w2.shape[-1])


# ----------------------------------------------------------------------
# Elementwise command set
# ----------------------------------------------------------------------
def elementwise(op: str, x: jnp.ndarray, y: jnp.ndarray | None = None,
                imm: float = 0.0) -> jnp.ndarray:
    if not _pallas():
        return ref.elementwise(op, x, y, imm)
    shape = x.shape
    flat = x.reshape(1, -1)
    yf = y.reshape(1, -1) if y is not None else None
    block = 1024 if flat.shape[1] >= 1024 else 128
    xf, n0 = _pad_to(flat, 1, block)
    if yf is not None:
        yf, _ = _pad_to(yf, 1, block)
    out = elementwise_pallas(op, xf, yf, imm=imm, block=block,
                             interpret=_interp())
    return out[:, :n0].reshape(shape)


def axpy(a: float, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return elementwise("axpy", x, y, imm=a)


def elementwise_chain(stages, x: jnp.ndarray, ys=(),
                      block: int | None = None) -> jnp.ndarray:
    """Fused chain of streaming commands: one pass over ``x``.

    ``stages``: sequence of (op, imm). Each 2-read op consumes the next
    array from ``ys``. Equivalent to folding ``elementwise`` over the
    stages, but the value never leaves registers between stages.

    An explicit ``block`` requests a *double-buffered grid* on the Pallas
    backends: the grid runs sequentially and the Mosaic pipeline copies
    block i+1 in under block i's compute — the TCDM scheme of
    ``core.memory``/``core.tiling`` realised natively. Size it from the
    memory model: ``NtxMemSpec.pallas_block_elems(n_streams)``. ``None``
    keeps the default parallel grid (and is a no-op on the ref backend).
    """
    stages = tuple((str(op), float(imm)) for op, imm in stages)
    ys = tuple(ys)
    if not _pallas():
        val = x
        yi = 0
        for op, imm in stages:
            y = None
            if op in _OPS2:
                y = ys[yi]
                yi += 1
            val = ref.elementwise(op, val, y, imm)
        return val
    shape = x.shape
    flat = x.reshape(1, -1)
    double_buffer = block is not None
    if block is None:
        block = 1024 if flat.shape[1] >= 1024 else 128
    xf, n0 = _pad_to(flat, 1, block)
    yfs = []
    for y in ys:
        yf, _ = _pad_to(y.reshape(1, -1), 1, block)
        yfs.append(yf)
    out = elementwise_chain_pallas(stages, xf, tuple(yfs), block=block,
                                   interpret=_interp(),
                                   double_buffer=double_buffer)
    return out[:, :n0].reshape(shape)


def chain_reduce(stages, red: str, x: jnp.ndarray, ys=()):
    """Fused chain + reduction tail over the last axis of (rows, n).

    ``stages`` as in :func:`elementwise_chain`; ``red`` is one of
    sum/min/max/argmin/argmax. Returns ``(chain_out (rows, n), reduction
    (rows,))`` — the chain value is materialized once AND reduced
    in-register in the same pass (the descriptor stream's chain ->
    VSUM/MAX tail, e.g. a softmax-style masked-probability sum). The arg
    tails return the winning int32 index (the comparator + index-counter
    datapath; ties resolve first-wins, like ``np.argmax``).
    """
    stages = tuple((str(op), float(imm)) for op, imm in stages)
    ys = tuple(ys)
    if not _pallas():
        val = x
        yi = 0
        for op, imm in stages:
            y = None
            if op in _OPS2:
                y = ys[yi]
                yi += 1
            val = ref.elementwise(op, val, y, imm)
        return val, ref.reduce(red, val)
    rows, n = x.shape
    block = 512 if n >= 512 else 128
    xf, n0 = _pad_to(x, 1, block)
    yfs = tuple(_pad_to(y, 1, block)[0] for y in ys)
    out, red_v = chain_reduce_pallas(stages, red, xf, yfs, n_valid=n0,
                                     block=block, interpret=_interp())
    red_v = red_v[:, 0]
    if red in ("argmin", "argmax"):
        red_v = red_v.astype(jnp.int32)      # ref-path parity
    return out[:, :n0], red_v


# ----------------------------------------------------------------------
# Reductions
# ----------------------------------------------------------------------
_PAD_VALUE = {"sum": 0.0, "min": np.inf, "max": -np.inf,
              "argmin": np.inf, "argmax": -np.inf}


def reduce(op: str, x: jnp.ndarray) -> jnp.ndarray:
    """Reduce over the last axis of (rows, n)."""
    if not _pallas():
        return ref.reduce(op, x)
    block = 512 if x.shape[-1] >= 512 else 128
    xp, _ = _pad_to(x, 1, block, value=_PAD_VALUE[op])
    return reduce_pallas(op, xp, block=block, interpret=_interp())


# ----------------------------------------------------------------------
# Convolution (the Pallas grid walks output tiles, as the RISC-V host does)
# ----------------------------------------------------------------------
def conv2d(img: jnp.ndarray, ker: jnp.ndarray) -> jnp.ndarray:
    if not _pallas():
        return ref.conv2d(img, ker)
    return conv2d_pallas(img, ker, interpret=_interp())


# ----------------------------------------------------------------------
# Stencils (paper §III-B3: a star stencil decomposes into 1-D passes per
# dimension; a pass along the last axis is a valid correlation with a
# (1, k) tap row, so it runs on the tiled conv kernel)
# ----------------------------------------------------------------------
def stencil_axis(x: jnp.ndarray, coeffs: jnp.ndarray, axis: int) -> jnp.ndarray:
    if not _pallas():
        return ref.stencil_axis(x, list(np.asarray(coeffs)), axis)
    x2 = jnp.moveaxis(x, axis, -1)
    lead = x2.shape[:-1]
    rows = int(np.prod(lead)) if lead else 1
    out = conv2d_pallas(x2.reshape(rows, x2.shape[-1]),
                        jnp.asarray(coeffs, jnp.float32).reshape(1, -1),
                        interpret=_interp())
    out = out.reshape(*lead, out.shape[-1])
    return jnp.moveaxis(out, -1, axis)


def laplace(x: jnp.ndarray) -> jnp.ndarray:
    """n-D discrete Laplace via per-axis passes (paper's decomposition)."""
    if not _pallas():
        return ref.laplace(x)
    nd = x.ndim
    coeffs = jnp.asarray([1.0, -2.0, 1.0], jnp.float32)
    core = tuple(slice(1, -1) for _ in range(nd))
    out = None
    for d in range(nd):
        sl = [slice(1, -1)] * nd
        sl[d] = slice(None)
        term = stencil_axis(x[tuple(sl)], coeffs, d)
        out = term if out is None else out + term
    return out


# ----------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------
def _flash_block(n: int, cap: int) -> int:
    """Largest 8-aligned b <= cap with n % b == 0 (the flash kernel needs
    exact divisibility and Mosaic needs sublane-aligned blocks). Returns 0
    when no such block exists (caller falls back to the ref path)."""
    for b in range(min(cap, n), 7, -1):
        if b % 8 == 0 and n % b == 0:
            return b
    return 0


def _attention_chain_reduce(q, k, v, *, causal, scale, q_offset):
    """Attention for shapes the flash kernel cannot tile, composed from the
    streaming command set: per-row MAX for the stabilizer, then the masked
    probabilities and their softmax normalizer in ONE fused pass — the
    MASK chain stage feeding a VSUM tail (``chain_reduce``)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    qg = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg,
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        valid = (jnp.arange(skv)[None, :] <= qpos).astype(jnp.float32)
    else:
        valid = jnp.ones((sq, skv), jnp.float32)
    validf = jnp.broadcast_to(valid[None, None, None], logits.shape)
    rows = b * hkv * g * sq
    lm = jnp.where(validf > 0, logits, -1e30).reshape(rows, skv)
    m = reduce("max", lm)
    p = jnp.exp(lm - m[:, None])
    pm, denom = chain_reduce([("mask", 0.0)], "sum", p,
                             ys=(validf.reshape(rows, skv),))
    pm = (pm / denom[:, None]).reshape(b, hkv, g, sq, skv)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", pm, v.astype(jnp.float32))
    return out.reshape(b, hq, sq, v.shape[-1]).astype(q.dtype)


def attention(q, k, v, *, causal: bool = True, scale=None,
              kv_len: int | None = None) -> jnp.ndarray:
    """q: (b, hq, sq, d); k/v: (b, hkv, skv, d)."""
    if not _pallas() or q.shape[-1] != v.shape[-1]:
        skv = k.shape[2]
        eff = skv if kv_len is None else kv_len
        # causal masking with q at absolute position eff - sq + i also hides
        # cache slots >= kv_len; non-causal callers pass full-length kv.
        q_offset = eff - q.shape[2]
        if q.shape[2] >= 512 and skv >= 2048 and skv % 512 == 0 and causal:
            # KV-blocked online softmax: O(sq*block) memory (flash pattern
            # at the XLA level) — required for the 32k train/prefill cells.
            # Decode (sq ~ 1) keeps the direct form: its logits are tiny and
            # the kv-block scan would fight the seq-sharded cache layout.
            return ref.mha_blocked(q, k, v, causal=True, scale=scale,
                                   q_offset=q_offset)
        return ref.mha(q, k, v, causal=causal, scale=scale,
                       q_offset=q_offset)
    sq, skv, d = q.shape[2], k.shape[2], q.shape[-1]
    # scheduler-sized blocks (autotune cache), shrunk to aligned divisors
    # of the actual sequence lengths as the flash kernel requires
    bm, bn, _ = matmul_blocks(sq, skv, d)
    bq = _flash_block(sq, bm) if sq >= 8 else sq
    bk = _flash_block(skv, bn)
    if bq == 0 or bk == 0:
        # no aligned block divides the sequence (e.g. prime lengths): the
        # flash kernel cannot tile it — compose the online softmax from
        # the streaming command set (MASK chain -> VSUM tail in one pass)
        eff = skv if kv_len is None else kv_len
        return _attention_chain_reduce(q, k, v, causal=causal, scale=scale,
                                       q_offset=eff - sq)
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  kv_len=kv_len, block_q=bq,
                                  block_k=bk, interpret=_interp())


# ----------------------------------------------------------------------
# SSD scan
# ----------------------------------------------------------------------
def ssd(x, dt, A, B, C, chunk: int = 64,
        work_dtype=jnp.float32) -> jnp.ndarray:
    """x: (b, l, h, dh); dt: (b, l, h); A: (h,); B/C: (b, l, n)."""
    if not _pallas():
        return ref.ssd_scan_chunked(x, dt, A, B, C, chunk=chunk,
                                    work_dtype=work_dtype) \
            if x.shape[1] % chunk == 0 else ref.ssd_scan(x, dt, A, B, C)
    b, l, h, dh = x.shape
    n = B.shape[-1]
    xs = jnp.moveaxis(x, 2, 1).reshape(b * h, l, dh)
    dts = jnp.moveaxis(dt, 2, 1).reshape(b * h, l)
    Bs = jnp.broadcast_to(B[:, None], (b, h, l, n)).reshape(b * h, l, n)
    Cs = jnp.broadcast_to(C[:, None], (b, h, l, n)).reshape(b * h, l, n)
    As = jnp.broadcast_to(A[None], (b, h)).reshape(b * h)
    y = ssd_scan_pallas(xs, dts, As, Bs, Cs, chunk=chunk, interpret=_interp())
    return jnp.moveaxis(y.reshape(b, h, l, dh), 1, 2)


# ----------------------------------------------------------------------
# Fused optimizer
# ----------------------------------------------------------------------
def adamw_update(p, g, m, v, step, *, lr, b1=0.9, b2=0.999, eps=1e-8,
                 wd=0.01):
    if not _pallas():
        return ref.adamw_update(p, g, m, v, step, lr, b1, b2, eps, wd)
    shape = p.shape
    flat = lambda t: t.reshape(1, -1)
    block = 1024 if p.size >= 1024 else 128
    pf, n0 = _pad_to(flat(p), 1, block)
    gf, _ = _pad_to(flat(g), 1, block)
    mf, _ = _pad_to(flat(m), 1, block)
    vf, _ = _pad_to(flat(v), 1, block)
    po, mo, vo = adamw_pallas(pf, gf, mf, vf, step, lr=lr, b1=b1, b2=b2,
                              eps=eps, wd=wd, block=block,
                              interpret=_interp())
    unflat = lambda t: t[:, :n0].reshape(shape)
    return unflat(po), unflat(mo), unflat(vo)
