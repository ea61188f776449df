"""Public jit'd wrappers around the Pallas kernels.

These handle the shape hygiene the raw kernels refuse to (padding to
block multiples, masking padded keys), pick block sizes, and fall back
to the jnp oracle for shapes too small to tile — so callers (VPE
variants, model layers) can use them unconditionally.

Kernels run natively on a TPU backend and through the Pallas interpreter
everywhere else (:func:`compat.default_interpret`, read per call).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .compat import default_interpret
from .conv2d import conv2d_pallas
from .flash_attention import flash_attention_pallas
from .matmul import matmul_pallas


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bk: int = 256, bn: int = 128) -> jax.Array:
    """Tiled Pallas matmul with automatic padding; oracle for tiny shapes."""
    m, k = a.shape
    _, n = b.shape
    if m < 8 or n < 8 or k < 8:
        return ref.matmul_ref(a, b)
    bm, bk, bn = min(bm, _round_up(m, 8)), min(bk, _round_up(k, 8)), min(bn, _round_up(n, 8))
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    ap = _pad_axis(_pad_axis(a, 0, mp), 1, kp)
    bp = _pad_axis(_pad_axis(b, 0, kp), 1, np_)
    out = matmul_pallas(ap, bp, bm=bm, bk=bk, bn=bn, interpret=default_interpret())
    return out[:m, :n]


def conv2d(x: jax.Array, w: jax.Array, *, bh: int = 8) -> jax.Array:
    """Valid 2-D cross-correlation via the Pallas kernel."""
    h, wid = x.shape
    kh, kw = w.shape
    h_out = h - kh + 1
    if h_out < bh or wid - kw + 1 < 8:
        return ref.conv2d_ref(x, w)
    hp_out = _round_up(h_out, bh)
    xp = _pad_axis(x, 0, hp_out + kh - 1)
    out = conv2d_pallas(xp, w, bh=bh, interpret=default_interpret())
    return out[:h_out]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    bq: int = 128,
    bk: int = 128,
) -> jax.Array:
    """Padded flash attention; q (B,Hq,S,D), k/v (B,Hkv,T,D)."""
    B, Hq, S, D = q.shape
    T = k.shape[2]
    bq = min(bq, _round_up(S, 8))
    bk = min(bk, _round_up(T, 8))
    sp, tp = _round_up(S, bq), _round_up(T, bk)
    qp = _pad_axis(q, 2, sp)
    kp = _pad_axis(k, 2, tp)
    vp = _pad_axis(v, 2, tp)
    out = flash_attention_pallas(
        qp, kp, vp,
        causal=causal, window=window, scale=scale,
        bq=bq, bk=bk, t_valid=T, q_offset=T - S,
        interpret=default_interpret(),
    )
    return out[:, :, :S, :]
