"""NTX direct 2-D convolution (paper §III-B2) as a Pallas kernel.

The silicon runs conv as a 3-deep descriptor (kernel-col, kernel-row,
out-col) while the RISC-V host iterates output rows / tiles. On TPU the
Pallas grid plays the host: it walks (row block, column block) output
tiles, and the kernel computes one tile with the kernel taps fully
unrolled (they are the two innermost HWLs — static loops), accumulating in
fp32 (PCS register).

A tile needs ``kh - 1`` rows and ``kw - 1`` columns beyond its own block
(the halo). Blocked specs cannot overlap, so the halo arrives as extra
operands on the same image: the block below, the block to the right and
the corner block, each one halo-sized block of its own. Every operand
block meets the TPU's 8 x 128 tiling rule, and every shift inside the
kernel is a static slice of a VMEM value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes of one input block; with double buffering, the halo operands,
#: the output block and the kernel's temporaries this stays well inside
#: the default scoped VMEM
_BLOCK_BYTES = 1 << 20


def _align_up(x: int, mult: int) -> int:
    return max(mult, -(-x // mult) * mult)


def _conv_kernel(ker_ref, *refs, kh: int, kw: int, bm: int, bn: int):
    out_ref = refs[-1]
    main, rest = refs[0], list(refs[1:-1])
    x = main[...]
    right = rest.pop(0)[...] if kw > 1 else None
    bottom = rest.pop(0)[...] if kh > 1 else None
    corner = rest.pop(0)[...] if kh > 1 and kw > 1 else None
    if right is not None:
        x = jnp.concatenate([x, right], axis=1)
    if bottom is not None:
        if corner is not None:
            bottom = jnp.concatenate([bottom, corner], axis=1)
        x = jnp.concatenate([x, bottom], axis=0)
    x = x.astype(jnp.float32)
    acc = jnp.zeros((bm, bn), jnp.float32)
    for i in range(kh):                          # HWL1: kernel row (unrolled)
        for j in range(kw):                      # HWL0: kernel col (unrolled)
            acc = acc + ker_ref[i, j] * x[i:i + bm, j:j + bn]
    out_ref[...] = acc.astype(out_ref.dtype)


def conv2d_pallas(img: jnp.ndarray, ker: jnp.ndarray,
                  interpret: bool = False) -> jnp.ndarray:
    """Valid 2-D correlation of one (H, W) plane with (kh, kw) taps.

    Any plane size: the image is zero-padded up to whole output tiles plus
    one halo block, and the result is cut back to (H - kh + 1, W - kw + 1).
    A tile is up to 2048 columns wide and about ``_BLOCK_BYTES`` large
    (its height rounded up to whole halo blocks).
    """
    h, w = img.shape
    kh, kw = ker.shape
    oh, ow = h - kh + 1, w - kw + 1
    hr = _align_up(kh - 1, 8)                    # halo rows (sublane tiles)
    hc = _align_up(kw - 1, 128)                  # halo cols (lane tiles)
    bn = _align_up(min(ow, 2048), hc)
    bm = _BLOCK_BYTES // (4 * bn)
    bm = _align_up(min(bm, oh), hr)
    gm, gn = -(-oh // bm), -(-ow // bn)
    xp = jnp.pad(img, ((0, gm * bm + hr - h), (0, gn * bn + hc - w)))
    operands = [xp]
    specs = [pl.BlockSpec((bm, bn), lambda i, j: (i, j))]
    if kw > 1:                                   # right halo
        operands.append(xp)
        specs.append(pl.BlockSpec(
            (bm, hc), lambda i, j: (i, (j + 1) * (bn // hc))))
    if kh > 1:                                   # bottom halo
        operands.append(xp)
        specs.append(pl.BlockSpec(
            (hr, bn), lambda i, j: ((i + 1) * (bm // hr), j)))
    if kh > 1 and kw > 1:                        # corner halo
        operands.append(xp)
        specs.append(pl.BlockSpec(
            (hr, hc), lambda i, j: ((i + 1) * (bm // hr),
                                    (j + 1) * (bn // hc))))
    out = pl.pallas_call(
        functools.partial(_conv_kernel, kh=kh, kw=kw, bm=bm, bn=bn),
        grid=(gm, gn),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(ker.astype(jnp.float32), *operands)
    return out[:oh, :ow]
