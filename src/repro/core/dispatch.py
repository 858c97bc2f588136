"""NTX command decoder for TPU: Descriptor -> kernel dispatch.

The silicon's controller decodes a descriptor and issues micro-instructions
to the FPU; this module is the TPU analogue — it pattern-matches a
descriptor against the kernel suite (GEMM/GEMV panels, the elementwise
command set, reductions, 2-D correlations: conv and stencil passes) and
dispatches to the corresponding ``repro.kernels.ops`` entry point (Pallas
on TPU, oracle elsewhere). A loop nest with no blocked equivalent runs as
the engine's jittable gather/reduce plan (``engine.execute_jax``) — on the
device, like every other path; a nest with no on-device plan at all is an
error, never a host round trip. Round-trips are validated against
``engine.execute`` in tests/test_dispatch.py.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import ops
from . import engine
from .descriptor import Agu, Descriptor, Opcode

_EW_OPS = {Opcode.AXPY: "axpy", Opcode.ADD: "add", Opcode.SUB: "sub",
           Opcode.MUL: "mul", Opcode.MASK: "mask", Opcode.RELU: "relu",
           Opcode.THRESH: "thresh", Opcode.COPY: "copy", Opcode.SET: "set"}
_RED_OPS = {Opcode.VSUM: "sum", Opcode.MIN: "min", Opcode.MAX: "max",
            Opcode.ARGMIN: "argmin", Opcode.ARGMAX: "argmax"}


def _is_contiguous_1d(desc: Descriptor) -> bool:
    return (len(desc.bounds) == 1
            and desc.agu0.strides[0] in (0, 1)
            and desc.agu1.strides[0] in (0, 1)
            and desc.agu2.strides[0] in (0, 1))


def _match_gemm(desc: Descriptor) -> Optional[tuple]:
    """C[m,n] = A[m,k] @ B[k,n] with the canonical AGU pattern."""
    if (desc.opcode is not Opcode.MAC or len(desc.bounds) != 3
            or desc.init_level != 1 or desc.store_level != 1):
        return None
    k, n, m = desc.bounds
    a0, a1, a2 = desc.agu0, desc.agu1, desc.agu2
    if (a0.strides[:3] == (1, 0, k) and a1.strides[:3] == (n, 1, 0)
            and a2.strides[:3] == (0, 1, n)):
        return m, n, k
    return None


def _match_gemv(desc: Descriptor) -> Optional[tuple]:
    if (desc.opcode is not Opcode.MAC or len(desc.bounds) != 2
            or desc.init_level != 1 or desc.store_level != 1):
        return None
    n, m = desc.bounds
    a0, a1, a2 = desc.agu0, desc.agu1, desc.agu2
    if (a0.strides[1] == n and a0.strides[0] == 1
            and a1.strides[:2] == (1, 0) and a2.strides[:2] == (0, 1)):
        return m, n
    return None


def _matches_reduce(desc: Descriptor) -> bool:
    return (desc.opcode in _RED_OPS and len(desc.bounds) == 1
            and desc.init_level == 1 and desc.store_level == 1
            and desc.agu0.strides[0] == 1)


def _match_correlation(desc: Descriptor) -> Optional[tuple]:
    """2-D valid correlation of a pitched image window with a dense tap
    block: a conv (two tap loops: kernel col, kernel row) or a 1-D stencil
    pass (one tap loop, along the row or down the column), then the output
    column loop and optionally the output row loop.

    Returns (rows, cols, kh, kw, pitch, out_pitch) or None."""
    t = desc.init_level
    n = len(desc.bounds)
    if (desc.opcode is not Opcode.MAC or desc.store_level != t
            or t not in (1, 2) or n not in (t + 1, t + 2)):
        return None
    s0, s1, s2 = desc.agu0.strides, desc.agu1.strides, desc.agu2.strides
    has_rows = n == t + 2
    cols = desc.bounds[t]
    rows = desc.bounds[t + 1] if has_rows else 1
    out_pitch = s2[t + 1] if has_rows else cols
    if ((s0[t], s1[t], s2[t]) != (1, 0, 1) or any(s2[:t])
            or (has_rows and (s1[t + 1] != 0 or out_pitch < cols))):
        return None
    if t == 2:                                   # conv: kw inner, kh outer
        kw, kh = desc.bounds[0], desc.bounds[1]
        pitch = s0[1]
        if ((s0[0], s1[0], s1[1]) != (1, 1, kw)
                or (has_rows and s0[t + 1] != pitch)):
            return None
    else:                                        # stencil pass: k taps
        k = desc.bounds[0]
        pitch = s0[t + 1] if has_rows else cols + k
        if s1[0] != 1:
            return None
        if s0[0] == 1:
            kh, kw = 1, k
        elif has_rows and s0[0] == pitch:
            kh, kw = k, 1
        else:
            return None
    if rows + kh - 1 > 1 and pitch < cols + kw - 1:
        return None                              # window rows would overlap
    return rows, cols, kh, kw, pitch, out_pitch


def _window(mem: jnp.ndarray, base: int, rows: int, cols: int,
            pitch: int) -> jnp.ndarray:
    """The (rows, cols) block of a plane with row pitch ``pitch`` at
    ``base`` (the last row may end at the image's end)."""
    if rows == 1:
        return mem[base:base + cols][None]
    seg = mem[base:base + (rows - 1) * pitch + cols]
    seg = jnp.pad(seg, (0, rows * pitch - seg.shape[0]))
    return seg.reshape(rows, pitch)[:, :cols]


def _store_window(mem: jnp.ndarray, base: int, val: jnp.ndarray,
                  pitch: int) -> jnp.ndarray:
    rows, cols = val.shape
    if rows == 1 or pitch == cols:
        return mem.at[base:base + rows * cols].set(val.reshape(-1))
    idx = (base + jnp.arange(rows)[:, None] * pitch
           + jnp.arange(cols)[None, :])
    return mem.at[idx.reshape(-1)].set(val.reshape(-1))


def dispatch(desc: Descriptor, mem: jnp.ndarray) -> jnp.ndarray:
    """Execute one NTX command on the flat memory via the kernel suite.

    Returns the updated memory (functional semantics, like the engine).
    """
    mem = jnp.asarray(mem, jnp.float32)

    if desc.num_iters == 0:     # zero-trip nest: no iterations, no stores
        return mem

    gm = _match_gemm(desc)
    if gm is not None:
        m, n, k = gm
        A = jnp.reshape(mem[desc.agu0.base:desc.agu0.base + m * k], (m, k))
        B = jnp.reshape(mem[desc.agu1.base:desc.agu1.base + k * n], (k, n))
        C = ops.gemm(A, B)
        return mem.at[desc.agu2.base:desc.agu2.base + m * n].set(
            C.reshape(-1))

    gv = _match_gemv(desc)
    if gv is not None:
        m, n = gv
        A = jnp.reshape(mem[desc.agu0.base:desc.agu0.base + m * n], (m, n))
        x = mem[desc.agu1.base:desc.agu1.base + n]
        y = ops.gemm(A, x[:, None])[:, 0]
        return mem.at[desc.agu2.base:desc.agu2.base + m].set(y)

    if desc.opcode in _EW_OPS and _is_contiguous_1d(desc):
        n = desc.bounds[0]
        x = mem[desc.agu0.base:desc.agu0.base + n][None]
        y = (mem[desc.agu1.base:desc.agu1.base + n][None]
             if desc.reads_per_iter >= 2 else None)
        out = ops.elementwise(_EW_OPS[desc.opcode], x, y, imm=desc.imm)
        return mem.at[desc.agu2.base:desc.agu2.base + n].set(out[0])

    if _matches_reduce(desc):
        n = desc.bounds[0]
        x = mem[desc.agu0.base:desc.agu0.base + n][None]
        red = ops.reduce(_RED_OPS[desc.opcode], x)
        return mem.at[desc.agu2.base].set(red[0].astype(jnp.float32))

    corr = _match_correlation(desc)
    if corr is not None:
        rows, cols, kh, kw, pitch, out_pitch = corr
        img = _window(mem, desc.agu0.base, rows + kh - 1, cols + kw - 1,
                      pitch)
        ker = mem[desc.agu1.base:desc.agu1.base + kh * kw].reshape(kh, kw)
        return _store_window(mem, desc.agu2.base, ops.conv2d(img, ker),
                             out_pitch)

    # no blocked kernel for this nest: the engine's jittable gather/reduce
    # plan, which covers every descriptor with store_level == init_level
    if not traceable_descriptor(desc):
        raise NotImplementedError(
            f"no on-device plan for {desc}: a prefix-store nest "
            f"(store_level {desc.store_level} < init_level "
            f"{desc.init_level}) runs only on engine.execute")
    return engine.execute_jax(desc, mem)


def traceable_descriptor(desc: Descriptor) -> bool:
    """True iff :func:`dispatch` can execute this descriptor, on the device
    and under a jax trace (vmap/shard_map multi-cluster execution): every
    kernel pattern and the jittable engine plan need
    store_level == init_level."""
    return desc.num_iters == 0 or desc.store_level == desc.init_level


# The deprecated ``dispatch_stream``/``dispatch_graph`` shims (PR 4)
# are gone: build a :class:`~repro.core.program.Program` and call
# :meth:`~repro.core.executor.Executor.run`, or use
# ``Executor.run_descriptors(descs, mem, policy=...)`` for raw
# descriptor lists (see docs/api.md for the migration table).
