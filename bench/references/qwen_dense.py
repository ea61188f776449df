"""Plain float32 forward pass of a Qwen-style dense decoder (Qwen2, Qwen3).

The architecture as published: pre-norm decoder layers, each RMSNorm ->
grouped-query attention with rotary positions -> residual, RMSNorm ->
SwiGLU -> residual; a final RMSNorm and an untied output head.  Qwen2
adds a bias to the q/k/v projections; Qwen3 instead normalises each
query and key head (RMSNorm over the head dimension) before the
rotation.  Everything runs in float32 at ``Precision.HIGHEST``, over
the whole sequence at once, with no cache, no kernels and no batching.

Weights come in the serving program's layout (one dict; layer weights
stacked on a leading axis), which is why two conventions follow it and
not the published checkpoints: matrices are (in, out), and the rotary
pairs are (2i, 2i+1).  The published form pairs (i, i + D/2); the two
agree once the q/k projection columns are permuted, which random
weights do not care about.

Nothing here imports the program.  The benchmark makes the weights
itself (:func:`init`), hands the same arrays to the program and to this
reference, and compares logits.

``mode="fp8"`` is the control: the same pass with every matrix product
taking float8 (e4m3) operands, each scaled by its largest magnitude
along the contracted axis, and float32 accumulation.  It is the step
below the configuration's bfloat16 that a later change could be tempted
to take, and the comparison has to catch it.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0            # largest finite float8_e4m3fn


def dims(cfg: Dict) -> Dict:
    """The sizes this reference needs, from a configuration file."""
    arch = cfg.get("architecture", {})
    return {
        "L": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
        "Hq": int(cfg["num_attention_heads"]),
        "Hkv": int(cfg["num_key_value_heads"]), "D": int(cfg["head_dim"]),
        "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "qk_norm": bool(arch.get("qk_norm", False)),
        "qkv_bias": bool(arch.get("qkv_bias", False)),
        "dtype": cfg["torch_dtype"],
    }


def shapes(c: Dict) -> Dict[Tuple[str, ...], Tuple[int, ...]]:
    """Every weight by its path in the program's parameter dict."""
    L, d, Hq, Hkv, D, F, V = (c[k] for k in ("L", "d", "Hq", "Hkv", "D",
                                             "F", "V"))
    layer = {
        "ln1": (d,), "ln2": (d,),
        "attn_wq": (d, Hq * D), "attn_wk": (d, Hkv * D),
        "attn_wv": (d, Hkv * D), "attn_wo": (Hq * D, d),
        "ffn_w_gate": (d, F), "ffn_w_up": (d, F), "ffn_w_down": (F, d),
    }
    if c["qkv_bias"]:
        layer.update({"attn_bq": (Hq * D,), "attn_bk": (Hkv * D,),
                      "attn_bv": (Hkv * D,)})
    if c["qk_norm"]:
        layer.update({"attn_q_norm": (D,), "attn_k_norm": (D,)})
    out = {("embed",): (V, d), ("final_norm",): (d,), ("lm_head",): (d, V)}
    out.update({("layers", k): (L,) + s for k, s in layer.items()})
    return out


def _draw(key, path: Tuple[str, ...], shape, dtype):
    """One weight: norm gains near 1, biases small, matrices scaled by
    their fan-in, the embedding as published models keep it (0.02)."""
    name = path[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm") or name in ("ln1", "ln2"):
        w = 1.0 + 0.1 * z
    elif name.startswith("attn_b"):
        w = 0.1 * z
    elif name == "embed":
        w = 0.02 * z
    else:
        w = z / math.sqrt(shape[-2])
    return w.astype(dtype)


def init(c: Dict, key):
    """All weights from one key, in the served dtype, in one program:
    each float32 draw fuses with its cast, so none reaches memory."""
    flat = {}
    for i, (path, shape) in enumerate(sorted(shapes(c).items())):
        flat[path] = _draw(jax.random.fold_in(key, i), path, shape,
                           jnp.dtype(c["dtype"]))
    params: Dict = {"layers": {}}
    for path, w in flat.items():
        if path[0] == "layers":
            params["layers"][path[1]] = w
        else:
            params[path[0]] = w
    return params


# -- the forward pass ---------------------------------------------------------

def _q8(x, axis):
    """float8 e4m3 rounding of ``x``, scaled per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, mode):
    """a (..., k) @ b (k, n) in float32; in fp8 mode both operands are
    first rounded to float8 along k."""
    if mode == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (H, T, D): rotate each pair (2i, 2i+1) by position x freq_i."""
    H, T, D = x.shape
    freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(H, T, D)


def _attention(q, k, v, mode, q_block):
    """Causal grouped-query attention, queries in blocks of ``q_block``
    rows so the score matrix stays small.  q (Hq, T, D), k/v (Hkv, T, D)."""
    Hq, T, D = q.shape
    g = Hq // k.shape[0]
    k = jnp.repeat(k, g, axis=0)
    v = jnp.repeat(v, g, axis=0)
    if mode == "fp8":
        k, v = _q8(k, -1), _q8(v, 1)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, axis=1)
        if mode == "fp8":
            qi = _q8(qi, -1)
        s = jnp.einsum("hqd,htd->hqt", qi, k, precision=HIGHEST) / math.sqrt(D)
        row = i * q_block + jnp.arange(q_block)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= row, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if mode == "fp8":
            p = _q8(p, -1)
        return jnp.einsum("hqt,htd->hqd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(T // q_block))   # (n, Hq, qb, D)
    return jnp.moveaxis(out, 0, 1).reshape(Hq, T, D)


@functools.partial(jax.jit, static_argnames=("c", "mode", "q_block"))
def _layer(x, layers, l, *, c, mode, q_block):
    """One decoder layer on x (T, d) float32, weights of layer ``l``
    taken from the stacked dict and widened to float32 here."""
    w = {k: jax.lax.dynamic_index_in_dim(a, l, keepdims=False)
         .astype(jnp.float32) for k, a in layers.items()}
    T = x.shape[0]
    Hq, Hkv, D = c["Hq"], c["Hkv"], c["D"]
    h = _rms(x, w["ln1"], c["eps"])
    q, k, v = (_mm(h, w[n], mode) for n in ("attn_wq", "attn_wk", "attn_wv"))
    if c["qkv_bias"]:
        q, k, v = q + w["attn_bq"], k + w["attn_bk"], v + w["attn_bv"]
    q = q.reshape(T, Hq, D).transpose(1, 0, 2)
    k = k.reshape(T, Hkv, D).transpose(1, 0, 2)
    v = v.reshape(T, Hkv, D).transpose(1, 0, 2)
    if c["qk_norm"]:
        q = _rms(q, w["attn_q_norm"], c["eps"])
        k = _rms(k, w["attn_k_norm"], c["eps"])
    q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
    o = _attention(q, k, v, mode, q_block)
    o = o.transpose(1, 0, 2).reshape(T, Hq * D)
    x = x + _mm(o, w["attn_wo"], mode)
    h = _rms(x, w["ln2"], c["eps"])
    a = (jax.nn.silu(_mm(h, w["ffn_w_gate"], mode))
         * _mm(h, w["ffn_w_up"], mode))
    return x + _mm(a, w["ffn_w_down"], mode)


@jax.jit
def _embed(embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, g, *, eps):
    return _rms(x, g.astype(jnp.float32), eps)


def hidden(params, c: Dict, tokens, mode: str = "f32", q_block: int = 512):
    """Final-normed hidden states (T, d) of ``tokens`` (T,), one layer
    at a time.  T must be a multiple of ``q_block``; causal attention
    keeps right padding out of every real position."""
    hc = _frozen(c)
    x = _embed(params["embed"], tokens)
    for l in range(c["L"]):
        x = _layer(x, params["layers"], l, c=hc, mode=mode, q_block=q_block)
    return _final(x, params["final_norm"], eps=c["eps"])


@jax.jit
def _gap_block(h, head, chosen):
    """Rows h (n, d): the float32 head's best logit minus its logit of
    ``chosen``.  Both come from one logits array, the best through one
    ``top_k``."""
    logits = jnp.matmul(h, head.astype(jnp.float32), precision=HIGHEST)
    top, _ = jax.lax.top_k(logits, 1)
    picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)
    return top[:, 0] - picked[:, 0]


@functools.partial(jax.jit, static_argnames=("mode",))
def _pick_block(h, head, *, mode):
    return jnp.argmax(_mm(h, head.astype(jnp.float32), mode),
                      axis=-1).astype(jnp.int32)


def _padded(values, block: int):
    """``values`` as int32, zero-padded to whole blocks (one program
    shape for every block), and the blocks' starts."""
    n = len(values)
    out = jnp.zeros((-(-n // block) * block,), jnp.int32)
    out = out.at[:n].set(jnp.asarray(values, jnp.int32))
    return out, range(0, len(out), block)


def gaps(params, h, positions, chosen, block: int = 256):
    """How far the reference's logit of ``chosen[i]`` lies below its
    best logit at row ``positions[i]`` of the hidden states ``h``."""
    pos, starts = _padded(positions, block)
    ch, _ = _padded(chosen, block)
    return jnp.concatenate([
        _gap_block(h[pos[a:a + block]], params["lm_head"], ch[a:a + block])
        for a in starts])[:len(positions)]


def picks(params, h, positions, mode: str, block: int = 256):
    """The token a ``mode`` head puts first at each of ``positions``
    (``h`` should come from :func:`hidden` in the same mode)."""
    pos, starts = _padded(positions, block)
    return jnp.concatenate([
        _pick_block(h[pos[a:a + block]], params["lm_head"], mode=mode)
        for a in starts])[:len(positions)]


class _frozen(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
