"""Paged attention — block-indirect KV reads for the serve engine.

Two kernels read the page pool through a block table:
:func:`paged_attention_pallas` (single-query decode) and
:func:`paged_prefill_attention_pallas` (multi-query chunked prefill —
a whole Q-chunk scored per page, per-row causal frontiers).

The paged KV layout (models/kvcache.py, runtime/serve_loop.py) stores
every sequence as a *block table* of page ids into one shared pool, so
that prefix-cache admission can alias cached pages instead of copying
them.  Both kernels read those pages **in place**: no (B, T, D)
linearized copy of the KV ever exists in HBM.  (The jnp data path the
engine uses on CPU — ``kvcache.paged_gather_layer`` + the stock decode
attention — materializes exactly that copy; these kernels are what
remove it on a real TPU.)

Decode (:func:`_pa_kernel`) runs one grid step per sequence:

* the block table and per-sequence lengths ride in as **scalar
  prefetch** operands (``pltpu.PrefetchScalarGridSpec``); the K/V pools
  stay in HBM (``pl.ANY``) and the body copies pages itself;
* the step visits only the **live pages**, ``first .. length // bs``
  (``first`` is the sliding window's first page, else 0): a loop with a
  dynamic trip count, so a dead page costs no grid step, copy or
  compute;
* a page is **one copy of all the pool's KV heads** (``pool.at[page]``,
  a contiguous (Hkv, bs, D) block), and ``P = 128 // bs`` pages make a
  block whose scores fill a 128-lane tile; the next block's copies are
  in flight while one computes (two buffer slots);
* **K and V are read once each**: the K pass writes each block's
  masked f32 scores into VMEM (at most ``nb * bs`` columns per query
  head) and keeps the running max; the denominator is then summed from
  those scores, exactly as ``jax.nn.softmax`` forms it; the V pass
  forms ``p`` against those final statistics and accumulates ``p @ v``
  for all ``Hq // Hkv`` query heads of each KV head.

Numerics: f32 scores, statistics and accumulation, validated against
``kernels.ref.paged_attention_ref`` (itself exact vs the contiguous
decode attention on identically-valued pages).  With ``read_dtype``
set, K, V and the normalized probabilities are rounded through it
before the products — the serve gather path's two casts
(``paged_gather_layer(..., out_dtype=SLOT_CACHE_DTYPE)``, then
``p.astype(v.dtype)`` in ``decode_attention``), which keep the two
decode backends token-parity-exact.  A post-normalization cast of ``p``
needs the final max and denominator before any value product; keeping
the scores in VMEM gives them without reading K twice.

Prefill keeps a grid (B, Hkv, nb) with the page dimension innermost and
sequential, an online softmax across pages in VMEM scratch (the same
recurrence as kernels/flash_attention.py), and skips whole pages
outside the causal or sliding-window range via ``pl.when``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .compat import CompilerParams, default_interpret

_NEG_INF = float("-inf")


def _pa_kernel(
    bt_ref, len_ref,            # scalar prefetch: (B, nb) pages, (B,) lengths
    q_ref, k_hbm, v_hbm,        # q block (1, Hkv, G, D); pools (N, Hkv, bs, D)
    o_ref,
    buf, sems, s_ref, m_ref, l_ref, acc_ref,
    *, bs: int, nb: int, P: int, window: Optional[int], scale: float,
    read_dtype,
):
    """One slot's decode: stream its live pages through VMEM, K then V.

    Stream item ``i`` is block ``i`` of K for ``i < n``, block ``i - n``
    of V after; a block is ``P`` consecutive pages of the live range,
    each copied for all of the shard's KV heads at once.  Item ``i + 1``
    is in flight while item ``i`` computes (two buffer slots).  The K
    pass writes the masked scores to ``s_ref`` and keeps the running
    max; the exact denominator is summed from ``s_ref``; the V pass
    forms ``p`` against those final statistics."""
    b = pl.program_id(0)
    Hkv = buf.shape[2]
    W = P * bs
    length = len_ref[b]
    last = jnp.minimum(length // bs, nb - 1)
    first = 0 if window is None else jnp.maximum(
        (length - window + 1) // bs, 0)
    n = (jnp.maximum(last - first + 1, 0) + P - 1) // P

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def live_pages(k):
        """Pages of block ``k`` inside the live range: its first ones."""
        return jnp.clip(last + 1 - (first + k * P), 0, P)

    def for_pages(lo, hi, body):
        def step(p, carry):
            body(p)
            return carry
        jax.lax.fori_loop(lo, hi, step, 0)

    def copy(pool, k, slot, p):
        page = bt_ref[b, first + k * P + p]
        return pltpu.make_async_copy(pool.at[page], buf.at[slot, p],
                                     sems.at[slot])

    def start(i):
        for pool, lo, hi in ((k_hbm, 0, n), (v_hbm, n, 2 * n)):
            @pl.when((i >= lo) & (i < hi))
            def _():
                for_pages(0, live_pages(i - lo),
                          lambda p: copy(pool, i - lo, i % 2, p).start())

    def wait(pool, k, slot):
        for_pages(0, live_pages(k), lambda p: copy(pool, k, slot, p).wait())

    def cols(k):
        col = (first + k * P) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, W), 1)
        # a length past the table (a free slot's stale one) reads it whole
        valid = (col <= length) & (col < (last + 1) * bs)
        if window is not None:
            valid &= col > length - window
        return valid

    def page_block(slot, h):
        x = buf[slot, :, h].reshape(W, buf.shape[-1])      # (P*bs, D)
        return x if read_dtype is None else x.astype(read_dtype)

    def dot(a, x, dims):
        # bf16 x bf16 products are exact in f32: the MXU's native pass
        # gives what f32 operands holding the same values would
        ct = jnp.promote_types(a.dtype, x.dtype)
        return jax.lax.dot_general(
            a.astype(ct), x.astype(ct), (dims, ((), ())),
            preferred_element_type=jnp.float32)

    def probs(k, h):
        s = s_ref[h, :, pl.ds(pl.multiple_of(k * W, W), W)]
        m = m_ref[h]
        return jnp.where(m == _NEG_INF, 0.0, jnp.exp(s - m))

    def k_block(k, carry):
        start(k + 1)
        slot = k % 2
        wait(k_hbm, k, slot)
        valid = cols(k)
        for h in range(Hkv):
            s = dot(q_ref[0, h], page_block(slot, h), ((1,), (1,))) * scale
            s = jnp.where(valid, s, _NEG_INF)                  # (G, P*bs)
            s_ref[h, :, pl.ds(pl.multiple_of(k * W, W), W)] = s
            m_ref[h] = jnp.maximum(m_ref[h], jnp.max(s, axis=1, keepdims=True))
        return carry

    def denominator(k, carry):
        for h in range(Hkv):
            l_ref[h] += jnp.sum(probs(k, h), axis=1, keepdims=True)
        return carry

    def v_block(k, carry):
        start(n + k + 1)
        slot = (n + k) % 2
        wait(v_hbm, k, slot)
        # a page past the live range was not copied: zero its stale rows,
        # whose p is 0 but which may hold anything
        def clear(p):
            buf[slot, p] = jnp.zeros(buf.shape[2:], buf.dtype)
        for_pages(live_pages(k), P, clear)
        for h in range(Hkv):
            l = l_ref[h]
            p = probs(k, h) / jnp.where(l == 0.0, 1.0, l)
            if read_dtype is not None:
                p = p.astype(read_dtype)
            acc_ref[h] += dot(p, page_block(slot, h), ((1,), (0,)))
        return carry

    start(0)
    jax.lax.fori_loop(0, n, k_block, 0)
    jax.lax.fori_loop(0, n, denominator, 0)
    jax.lax.fori_loop(0, n, v_block, 0)
    o_ref[0] = acc_ref[...].astype(o_ref.dtype)   # fully masked rows -> 0


def _pa_prefill_kernel(
    bt_ref, base_ref, lim_ref,  # scalar prefetch: (B, nb) pages, (B,) bases,
                                # (B,) column limits (= base + chunk_len)
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, bs: int, nb: int, C: int,
    window: Optional[int], scale: float,
):
    """Multi-query (chunked-prefill) body: the online-softmax recurrence
    of kernels/flash_attention.py over pages, every grid step scoring a
    whole chunk of ``C`` queries — rows of the (G*C, bs) score tile are query
    (g, i) at absolute position ``base + i``, so the causal frontier is
    per ROW rather than per sequence.  The chunk's own K/V are read from
    the pages like everything else (the engine writes-then-attends),
    which is exactly what makes prefill a multi-query special case of
    the decode indirection instead of a separate code path.

    The valid-column limit rides in as scalar prefetch (not a static),
    so the engine's chunk jits can pass the real token count as a traced
    scalar — including from inside ``lax.scan`` bodies — without
    recompiling per chunk length."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = base_ref[b]
    limit = lim_ref[b]
    col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    GC = m_ref.shape[0]
    # row r of the flattened (group, C) query tile is chunk position r % C
    row_pos = base + jax.lax.broadcasted_iota(jnp.int32, (GC, 1), 0)[:, 0] % C

    def body():
        q = q_ref[0, 0].astype(jnp.float32)            # (G*C, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                      # (G*C, bs)
        mask = (col[None, :] <= row_pos[:, None]) \
            & (col[None, :] < limit)
        if window is not None:
            mask &= col[None, :] > row_pos[:, None] - window
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]                            # (G*C, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(m_new == _NEG_INF, 0.0, jnp.exp(s - m_new))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # block sparsity: skip pages entirely past the LAST query's causal
    # frontier, and (SWA) entirely before the FIRST query's window
    live = j * bs <= limit - 1
    if window is not None:
        live &= (j * bs + bs - 1) > base - window
    pl.when(live)(body)

    @pl.when(j == nb - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_prefill_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    base: jax.Array,
    *,
    chunk_len=None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Chunked-prefill attention reading KV pages in place.

    q: (B, Hq, C, D) — a prompt chunk of C queries per sequence, query
    ``i`` at absolute position ``base[b] + i``; k_pool/v_pool:
    (N, Hkv, bs, D) one layer of the paged pool, with the chunk's own
    K/V already written into its pages; block_tables: (B, nb) int32;
    base: (B,) int32.  ``chunk_len`` caps valid columns at
    ``base + chunk_len`` — pass the real token count when C is padded;
    a python int or a traced int32 scalar/(B,) vector both work (it is
    folded into a scalar-prefetch operand, NOT a static arg, so the
    serve engine's chunk jits and fused ``lax.scan`` bodies never
    recompile on it).  Returns (B, Hq, C, D).

    The block table rides in as scalar prefetch, as for
    :func:`paged_attention_pallas`; grid step (b, h, j) DMAs pool page
    ``block_tables[b, j]`` through the K/V BlockSpec index maps, with
    all ``Hq // Hkv * C`` query rows of a KV head scored per page — the
    multi-query generalization the chunked-prefill engine path needs,
    so a 32k prompt's prefill touches each page once per chunk instead
    of materializing a linearized prefix copy in HBM.
    """
    B, Hq, C, D = q.shape
    N, Hkv, bs, _ = k_pool.shape
    nb = block_tables.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if interpret is None:
        interpret = default_interpret()
    if chunk_len is None:
        chunk_len = C
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    base = base.astype(jnp.int32)
    limit = jnp.broadcast_to(
        base + jnp.asarray(chunk_len, jnp.int32), base.shape)
    q4 = q.reshape(B, Hkv, group * C, D)
    kernel = functools.partial(
        _pa_prefill_kernel, bs=bs, nb=nb, C=C,
        window=window, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, group * C, D),
                         lambda b, h, j, bt, bs_, lm: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, j, bt, bs_, lm: (bt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D),
                         lambda b, h, j, bt, bs_, lm: (bt[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group * C, D),
                               lambda b, h, j, bt, bs_, lm: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group * C, 1), jnp.float32),
            pltpu.VMEM((group * C, 1), jnp.float32),
            pltpu.VMEM((group * C, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group * C, D), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables, base, limit, q4, k_pool, v_pool)
    return out.reshape(B, Hq, C, D)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "interpret", "read_dtype"))
def paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    read_dtype=None,
) -> jax.Array:
    """q: (B, Hq, 1, D); k_pool/v_pool: (N, Hkv, bs, D) one layer of the
    paged pool; block_tables: (B, nb) int32; lengths: (B,) int32 (the
    position being decoded).  Returns (B, Hq, 1, D).

    ``interpret`` defaults to true everywhere except a real TPU backend
    (``compat.default_interpret``); interpret mode runs the kernel body
    as stock jax ops — traceable under jit/scan — while on TPU each
    live page becomes one DMA of all the pool's KV heads.

    ``read_dtype`` (static) makes the kernel reproduce the gather
    path's quantization semantics end to end: K/V pages are
    round-tripped through that dtype (the values
    ``paged_gather_layer(..., out_dtype=SLOT_CACHE_DTYPE)`` reads), and
    the softmax probabilities are cast through it before the value
    matmul — the ``p.astype(v.dtype)`` in ``decode_attention``.  Both
    casts are what keeps the two decode backends token-parity-exact;
    omitting either leaves a ~4e-3 logit gap that flips greedy tokens
    over long generations.  Without it ``p`` stays in f32.
    """
    B, Hq, S, D = q.shape
    N, Hkv, bs, _ = k_pool.shape
    nb = block_tables.shape[1]
    assert S == 1, "paged decode attention is single-position"
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if interpret is None:
        interpret = default_interpret()
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    # a block of P pages scores P * bs keys: one 128-lane tile at bs <= 128
    P = max(1, 128 // bs)
    n_cols = pl.cdiv(nb, P) * P * bs
    q4 = q.reshape(B, Hkv, group, D)
    kernel = functools.partial(
        _pa_kernel, bs=bs, nb=nb, P=P, window=window, scale=scale,
        read_dtype=read_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, group, D), lambda b, bt, ln: (b, 0, 0, 0)),
            # the pools stay in HBM: the body copies the live pages
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, group, D),
                               lambda b, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, Hkv, bs, D), k_pool.dtype),   # page blocks
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Hkv, group, n_cols), jnp.float32),  # scores
            pltpu.VMEM((Hkv, group, 1), jnp.float32),       # max
            pltpu.VMEM((Hkv, group, 1), jnp.float32),       # denominator
            pltpu.VMEM((Hkv, group, D), jnp.float32),       # p @ v
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        compiler_params=CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(block_tables, lengths, q4, k_pool, v_pool)
    return out.reshape(B, Hq, 1, D)
