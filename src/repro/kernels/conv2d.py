"""2-D convolution kernel — the paper's Convolution / image-pipeline target.

The paper accelerates a naive square-kernel 2-D convolution (its contour
-detection demo) by 3.8x on the DSP.  TPU adaptation: the VPU is a
(8, 128) vector unit, so the natural decomposition is shift-and-MAC over
the (kh, kw) taps — each tap is one full-width vector FMA, unrolled at
trace time (kh*kw is small and static).  The output is blocked over
rows; the whole input frame stays resident in VMEM, so the frame size
bounds what compiles: the paper's 384x384 f32 frames fit, while a
2048x2048 f32 frame (16 MiB) exceeds v5e's 16 MiB scoped-VMEM limit and
the compiler refuses it.  Nothing chunks a larger frame before it
reaches the kernel.

Blocking the *output* only sidesteps the halo problem: overlapping input
windows cannot be expressed as disjoint BlockSpec tiles, so the kernel
reads its (bh + kh - 1)-row input slab with a dynamic slice instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import CompilerParams


def _conv_kernel(x_ref, w_ref, o_ref, *, bh: int, kh: int, kw: int, w_out: int):
    i = pl.program_id(0)
    # input slab for this output row-block: rows [i*bh, i*bh + bh + kh - 1)
    x = x_ref[pl.ds(i * bh, bh + kh - 1), :]
    acc = jnp.zeros((bh, w_out), jnp.float32)
    for di in range(kh):          # static unroll: kh*kw vector FMAs
        for dj in range(kw):
            acc += x[di:di + bh, dj:dj + w_out].astype(jnp.float32) * w_ref[di, dj]
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def conv2d_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    bh: int = 8,
    interpret: bool = True,
) -> jax.Array:
    """Valid cross-correlation: (H, W) * (kh, kw) -> (H-kh+1, W-kw+1).

    H - kh + 1 must be a multiple of bh (ops.py pads the image).
    """
    h, wid = x.shape
    kh, kw = w.shape
    h_out, w_out = h - kh + 1, wid - kw + 1
    assert h_out % bh == 0, (h_out, bh)
    grid = (h_out // bh,)
    return pl.pallas_call(
        functools.partial(_conv_kernel, bh=bh, kh=kh, kw=kw, w_out=w_out),
        grid=grid,
        in_specs=[
            # whole image resident in VMEM; kernel slices its slab
            pl.BlockSpec((h, wid), lambda i: (0, 0)),
            pl.BlockSpec((kh, kw), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bh, w_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h_out, w_out), x.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x, w)
