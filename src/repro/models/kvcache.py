"""KV / recurrent-state caches for serving.

Layout: per-layer arrays stacked on a leading L axis so the decode step
scans over (layer-weights, layer-cache) pairs.  The cache is statically
sized at ``max_len``; ``length`` is the number of valid positions.
Sliding-window archs keep a full-size cache here for simplicity of
indexing, but the *windowed* variant (``window_cache=True`` in the
sharding config) stores only ``window`` keys as a ring buffer — that is
what makes h2o-danube's 500k-context decode O(window) in memory.

Two length modes coexist:

* **scalar length** ``()`` — all batch rows advance in lockstep (the
  simple ``ServeLoop.generate`` path, ring buffers supported);
* **per-slot lengths** ``(B,)`` — each batch row is an independent
  decode *slot* with its own sequence length.  This is what the
  continuous-batching engine uses: a finished slot is re-primed
  mid-decode via :func:`insert_slot_kv` (the prompt's K/V overwrites
  positions ``[0, S)`` and ``length[slot]`` is reset, so the causal
  mask ``col <= length`` can never reach a previous occupant's stale
  entries).  Ring buffers are not supported in per-slot mode.

Every append in this module is a pure functional update (``.at[...]``
scatters / ``dynamic_update_slice``), which is what lets the fused
decode-horizon path carry caches and the page pool through a
``lax.scan`` over H steps (:func:`repro.models.transformer.
_horizon_scan`): :func:`update_layer_cache` writes at a per-slot
``length`` that the stop mask simply stops advancing for frozen slots
(their garbage re-writes land at the frozen position of their own
row), and :func:`append_token_paged`'s ``live`` mask doubles as the
freeze mask — a frozen slot's append is redirected to the trash page,
so reserved-but-unreached horizon pages stay untouched and can be
rolled back afterwards.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Cache = Dict[str, jax.Array]

# storage dtype of the serving KV caches (narrower than compute: the
# cast happens once at insert time — and the paged layout reproduces it
# at gather time, so both layouts read identical values)
SLOT_CACHE_DTYPE = jnp.bfloat16


def init_kv_cache(
    num_layers: int, batch: int, num_kv_heads: int, max_len: int, head_dim: int,
    dtype=SLOT_CACHE_DTYPE, *, per_slot: bool = False,
) -> Cache:
    shape = (num_layers, batch, num_kv_heads, max_len, head_dim)
    lshape = (batch,) if per_slot else ()
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "length": jnp.zeros(lshape, jnp.int32),
    }


def kv_cache_specs(
    num_layers: int, batch: int, num_kv_heads: int, max_len: int, head_dim: int,
    dtype=jnp.bfloat16, *, per_slot: bool = False,
) -> Dict[str, jax.ShapeDtypeStruct]:
    shape = (num_layers, batch, num_kv_heads, max_len, head_dim)
    lshape = (batch,) if per_slot else ()
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
        "length": jax.ShapeDtypeStruct(lshape, jnp.int32),
    }


def is_per_slot(length: jax.Array) -> bool:
    """True when ``length`` is the per-slot ``(B,)`` vector form."""
    return getattr(length, "ndim", 0) == 1


def update_layer_cache(
    k_cache: jax.Array, v_cache: jax.Array, k_new: jax.Array, v_new: jax.Array,
    length: jax.Array, *, ring_window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Insert (B, Hkv, S_new, D) keys at position ``length`` (no L axis).

    ``length`` may be a scalar (all rows write at the same position) or a
    per-slot ``(B,)`` vector (each row writes at its own position — the
    continuous-batching decode step).

    ring_window: if set, the cache holds only that many positions and
    writes wrap (ring buffer) — O(window) memory for SWA decode.  Only
    valid with a scalar length.
    """
    if is_per_slot(length):
        assert ring_window is None, "ring caches are lockstep-only"

        def upd(c, n, pos):
            return jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), pos, axis=1)

        k_cache = jax.vmap(upd)(k_cache, k_new, length)
        v_cache = jax.vmap(upd)(v_cache, v_new, length)
        return k_cache, v_cache
    if ring_window is not None:
        pos = length % ring_window
    else:
        pos = length
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), pos, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), pos, axis=2)
    return k_cache, v_cache


def insert_slot_kv(
    cache: Cache, k_new: jax.Array, v_new: jax.Array, slot: jax.Array,
    true_len: jax.Array,
) -> Cache:
    """Write a prefilled prompt's K/V into decode slot ``slot``.

    k_new/v_new: (L, 1, Hkv, S, D) stacked prompt keys/values (S may be
    bucket-padded; entries past ``true_len`` are garbage but unreachable
    through the causal mask).  Resets ``length[slot] = true_len`` — the
    slot-recycling contract: any stale positions the previous occupant
    wrote at ``>= true_len`` are masked until overwritten by new decode
    steps.
    """
    return insert_slot_kv_at(cache, k_new, v_new, slot, jnp.int32(0), true_len)


def insert_slot_kv_at(
    cache: Cache, k_new: jax.Array, v_new: jax.Array, slot: jax.Array,
    start_pos: jax.Array, true_len: jax.Array,
) -> Cache:
    """Write K/V pages into slot ``slot`` starting at position ``start_pos``.

    The offset form is the prefix-cache admission path: cached prefix
    pages are written at position 0, then the suffix prefill's K/V at
    ``start_pos = prefix_len`` (in that order — a bucket-padded prefix
    write may spill garbage past ``prefix_len``, which the suffix write
    then overwrites; anything beyond stays masked by ``length``).  The
    caller guarantees ``start_pos + S <= max_len`` so the update never
    clamps.  ``length[slot]`` is set to ``true_len`` (pass the FULL
    sequence length, not the write width).
    """
    zero = jnp.int32(0)
    slot = jnp.asarray(slot, jnp.int32)
    start = (zero, slot, zero, jnp.asarray(start_pos, jnp.int32), zero)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), start)
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), start)
    length = cache["length"].at[slot].set(jnp.asarray(true_len, jnp.int32))
    # preserve any layout-extension keys (kv_layout="auto" carries the
    # paged block table "bt" alongside the contiguous arrays)
    return {**cache, "k": k, "v": v, "length": length}


# -- block-granular KV page pool (shared-prefix cache) ------------------------
#
# The radix tree (runtime/prefix_cache.py) hands out integer page ids;
# these helpers own the device arrays behind them.  Pages are stored in
# the model's COMPUTE dtype, not the (possibly narrower) slot-cache
# dtype: a warm admission must hand the suffix prefill bit-identical
# prefix K/V to what a cold full prefill would have computed, otherwise
# greedy parity breaks.  The slot-cache cast happens at insert time on
# both paths, so downstream decode sees identical values either way.

def init_block_pool(
    num_blocks: int, num_layers: int, num_kv_heads: int, block_size: int,
    head_dim: int, dtype=jnp.float32,
) -> Cache:
    """Pool of KV pages: {"k","v"}: (N, L, Hkv, block_size, D)."""
    shape = (num_blocks, num_layers, num_kv_heads, block_size, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def write_block(
    pool: Cache, k_src: jax.Array, v_src: jax.Array, block_id: jax.Array,
    start: jax.Array, block_size: int,
) -> Cache:
    """Scatter one page: copy ``[start, start+block_size)`` of a prefill's
    stacked K/V (L, 1, Hkv, S, D) into pool page ``block_id``."""
    def cut(src):
        return jax.lax.dynamic_slice_in_dim(src, start, block_size, axis=3)[:, 0]
    return {
        "k": pool["k"].at[block_id].set(cut(k_src).astype(pool["k"].dtype)),
        "v": pool["v"].at[block_id].set(cut(v_src).astype(pool["v"].dtype)),
    }


def gather_blocks(pool: Cache, ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Gather pages ``ids`` (nb,) into contiguous prefix K/V.

    Returns (k, v) of shape (L, 1, Hkv, nb*block_size, D) — the layout
    :func:`insert_slot_kv_at` and the suffix prefill expect.  ``ids``
    may be padded (repeat any valid id); padded columns land past the
    true prefix length and are masked by the caller.
    """
    def take(p):
        g = p[ids]                                # (nb, L, Hkv, bs, D)
        nb, L, Hkv, bs, D = g.shape
        return g.transpose(1, 2, 0, 3, 4).reshape(L, Hkv, nb * bs, D)[:, None]
    return take(pool["k"]), take(pool["v"])


# -- paged (block-indirect) KV layout ----------------------------------------
#
# The unified page pool behind the paged KV layout: ONE id space shared
# by the radix tree's cached prefixes and live decode slots' block
# tables (host bookkeeping in runtime/page_pool.py).  Layout is
# LAYER-major — (L, N, Hkv, block_size, D) — unlike the PR 2 prefix
# pool's (N, L, ...): the decode step scans over layers, and a leading
# L axis lets the scan unstack per-layer pool slices without a
# whole-pool transpose per token.  The last row (index N-1 of the array,
# id ``num_pages`` of the allocator) is the TRASH page: free slots'
# garbage decode writes are redirected there so a scatter can run for
# the whole slot batch unconditionally.

def init_page_pool(
    num_pages: int, num_layers: int, num_kv_heads: int, block_size: int,
    head_dim: int, dtype=jnp.float32,
) -> Cache:
    """Unified paged pool: {"k","v"}: (L, num_pages + 1, Hkv, bs, D).

    ``dtype`` must be the model's COMPUTE dtype, exactly like the PR 2
    prefix pool: a warm suffix prefill must see bit-identical prefix
    K/V to what a cold full prefill would compute.  Decode reads are
    cast to the (possibly narrower) slot-cache dtype at gather time
    (see :func:`paged_gather_layer`), which reproduces the contiguous
    layout's insert-time cast — both parities (warm prefill AND decode)
    are structural, not empirical.  The memory price of the wider pool
    is the same one PR 2 already accepted for cached prefixes.
    """
    shape = (num_layers, num_pages + 1, num_kv_heads, block_size, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gather_pages(pool: Cache, ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Gather pages ``ids`` (nb,) from the layer-major pool.

    Returns (k, v) of shape (L, 1, Hkv, nb*block_size, D) — identical
    layout and values to :func:`gather_blocks` on the PR 2 pool, so the
    suffix prefill jit is shared between layouts (and its numerics are
    bitwise identical for identical page contents).  ``ids`` may be
    padded by repeating any valid id; padded columns land past the true
    prefix length and are masked by the caller.
    """
    def take(p):
        g = p[:, ids]                             # (L, nb, Hkv, bs, D)
        L, nb, Hkv, bs, D = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(L, Hkv, nb * bs, D)[:, None]
    return take(pool["k"]), take(pool["v"])


def write_pages(
    pool: Cache, k_src: jax.Array, v_src: jax.Array, ids: jax.Array,
    starts: jax.Array, base: jax.Array, valid_len: jax.Array,
) -> Cache:
    """Masked scatter of prefill K/V into pages — ONE dispatch per admission.

    k_src/v_src: (L, 1, Hkv, S_pad, D) stacked K/V covering prompt
    positions ``[base, base + S_pad)``; ``ids`` (nb,): target page ids
    (pad with the trash id — duplicate trash entries are harmless);
    ``starts`` (nb,): each page's absolute token start (block-aligned);
    ``valid_len``: number of REAL source positions (tokens past it are
    bucket padding).  For each page, columns whose absolute position
    falls outside ``[base, base + valid_len)`` keep their existing pool
    content — that is what makes the same dispatch serve full blocks,
    the copy-on-write tail block (written from ``base`` mid-block), and
    the final partial block.
    """
    bs = pool["k"].shape[3]
    src = starts[:, None] + jnp.arange(bs)[None, :] - base     # (nb, bs)
    valid = (src >= 0) & (src < valid_len)
    idx = jnp.clip(src, 0, k_src.shape[3] - 1)
    sel = valid[None, :, None, :, None]

    def put(pool_arr, src_arr):
        vals = src_arr[:, 0][:, :, idx]            # (L, Hkv, nb, bs, D)
        vals = vals.transpose(0, 2, 1, 3, 4).astype(pool_arr.dtype)
        old = pool_arr[:, ids]                     # (L, nb, Hkv, bs, D)
        return pool_arr.at[:, ids].set(jnp.where(sel, vals, old))

    return {"k": put(pool["k"], k_src), "v": put(pool["v"], v_src)}


def copy_page(pool: Cache, src: jax.Array, dst: jax.Array) -> Cache:
    """Copy-on-write: duplicate page ``src`` into ``dst`` (all layers)."""
    return {
        "k": pool["k"].at[:, dst].set(pool["k"][:, src]),
        "v": pool["v"].at[:, dst].set(pool["v"][:, src]),
    }


def swap_out_pages(pool: Cache, ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Preemption swap-out: gather a victim slot's filled pages so the
    host can hold their K/V while the pages are given away.

    Same contract and layout as :func:`gather_pages` — (k, v) of shape
    (L, 1, Hkv, nb*block_size, D), pages concatenated in ``ids`` order
    so position ``j*block_size + o`` of the result is block ``j``'s
    offset ``o`` — which is exactly the source indexing
    :func:`swap_in_pages` scatters back from.  ``ids`` may be padded by
    repeating any valid id; the caller records how many positions are
    real (its ``fill_pos``) and masks on the way back in.  The caller
    moves the result to host (``np.asarray``) — that copy IS the swap.
    """
    return gather_pages(pool, ids)


def swap_in_pages(
    pool: Cache, k_host: jax.Array, v_host: jax.Array, ids: jax.Array,
    starts: jax.Array, valid_len: jax.Array,
) -> Cache:
    """Preemption swap-in: scatter swapped-out K/V into fresh pages.

    ``k_host``/``v_host`` are a :func:`swap_out_pages` result (uploaded
    back to device), covering absolute positions ``[0, nb*block_size)``;
    ``ids`` are the newly allocated target pages (pad with the trash
    id), ``starts`` their block-aligned absolute token starts (pad with
    any negative start), and ``valid_len`` the number of REAL positions
    — the preempted residency's ``fill_pos``, so a half-filled tail
    block's stale columns keep the pool's existing content exactly as a
    mid-block prefill chunk would.  One masked scatter, the same
    dispatch :func:`write_pages` uses for admission.
    """
    return write_pages(pool, k_host, v_host, ids, starts,
                       jnp.int32(0), valid_len)


def write_chunk_paged_layer(
    pool_k_l: jax.Array, pool_v_l: jax.Array, k_new: jax.Array,
    v_new: jax.Array, bt_row: jax.Array, base: jax.Array,
    chunk_len: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter one prefill chunk's K/V into ONE slot's pages (one layer).

    k_new/v_new: (1, Hkv, C_pad, D) covering absolute positions
    ``[base, base + C_pad)`` (valid up to ``chunk_len``); bt_row: (nb,)
    the slot's full block-table row (trash-padded past its allocated
    pages).  For every (table column j, in-page offset o) the source
    index is ``j*bs + o - base``; positions outside
    ``[base, base + chunk_len)`` keep the existing pool content.  That
    single mask is what makes the same scatter serve every chunk shape:
    aliased prefix pages (all positions ``< base``) are never written, a
    copy-on-write tail page is written only from ``base`` on, a chunk
    ending mid-block leaves the rest of that page for the next chunk,
    and trash-padded columns write their own old content back (their
    positions land past ``chunk_len``).  This is the multi-token
    generalization of :func:`append_token_paged` — chunked prefill
    writes THEN attends through the table, exactly like decode.
    """
    bs = pool_k_l.shape[2]
    nb = bt_row.shape[0]
    src = (jnp.arange(nb) * bs)[:, None] + jnp.arange(bs)[None, :] - base
    valid = (src >= 0) & (src < chunk_len)                 # (nb, bs)
    idx = jnp.clip(src, 0, k_new.shape[2] - 1)
    sel = valid[:, None, :, None]

    def put(pool_arr, src_arr):
        vals = src_arr[0][:, idx]                          # (Hkv, nb, bs, D)
        vals = vals.transpose(1, 0, 2, 3).astype(pool_arr.dtype)
        old = pool_arr[bt_row]                             # (nb, Hkv, bs, D)
        return pool_arr.at[bt_row].set(jnp.where(sel, vals, old))

    return put(pool_k_l, k_new), put(pool_v_l, v_new)


def paged_gather_layer(pool_k_l: jax.Array, pool_v_l: jax.Array,
                       block_table: jax.Array,
                       out_dtype=None) -> Tuple[jax.Array, jax.Array]:
    """Linearize one layer's pages through block tables.

    pool_k_l/pool_v_l: (N, Hkv, bs, D) (one layer of the pool);
    block_table: (B, nb) page ids per slot.  Returns (B, Hkv, nb*bs, D)
    views where gathered column ``t`` holds absolute position ``t`` —
    the layout :func:`decode_attention` expects, so the contiguous
    decode-attention variants apply unchanged after the gather.  (This
    is the jnp reference data path; the Pallas kernel in
    ``kernels/paged_attention.py`` reads pages in place instead.)

    ``out_dtype``: the decode step passes the SLOT-CACHE dtype here.
    Pages are stored in the compute dtype (exact — warm suffix prefills
    must see bit-identical prefix K/V to a cold prefill, the PR 2
    rule), so casting the *read* to the slot-cache dtype reproduces
    exactly what the contiguous layout stored at insert time — that
    round-trip equality is what makes the two layouts' decode attention
    bitwise identical rather than merely close.
    """
    def take(p):
        g = p[block_table]                         # (B, nb, Hkv, bs, D)
        B, nb, Hkv, bs, D = g.shape
        g = g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, nb * bs, D)
        return g if out_dtype is None else g.astype(out_dtype)
    return take(pool_k_l), take(pool_v_l)


def append_token_paged(
    pool_k_l: jax.Array, pool_v_l: jax.Array, k_new: jax.Array,
    v_new: jax.Array, block_table: jax.Array, length: jax.Array,
    live: jax.Array, trash: int,
) -> Tuple[jax.Array, jax.Array]:
    """Write one decode step's K/V into each slot's tail page (one layer).

    k_new/v_new: (B, Hkv, 1, D); ``length`` (B,) is each slot's current
    position.  Non-live slots are redirected to the trash page — their
    block tables may still point at pages that were freed and
    reallocated to other slots, and a stale write there would corrupt a
    live request.  The engine guarantees a live slot's tail page is
    private (copy-on-write at admission), so the scatter never collides
    across live slots.
    """
    B = k_new.shape[0]
    bs = pool_k_l.shape[2]
    nb = block_table.shape[1]
    col = jnp.clip(length // bs, 0, nb - 1)
    page = jnp.where(live > 0, block_table[jnp.arange(B), col], trash)
    off = length % bs
    k_out = pool_k_l.at[page, :, off].set(k_new[:, :, 0].astype(pool_k_l.dtype))
    v_out = pool_v_l.at[page, :, off].set(v_new[:, :, 0].astype(pool_v_l.dtype))
    return k_out, v_out


def append_tokens_paged(
    pool_k_l: jax.Array, pool_v_l: jax.Array, k_new: jax.Array,
    v_new: jax.Array, block_table: jax.Array, length: jax.Array,
    write_mask: jax.Array, trash: int,
) -> Tuple[jax.Array, jax.Array]:
    """Write a SPAN of decode-position K/V into each slot's pages (one
    layer) — the multi-position generalization of
    :func:`append_token_paged` that the speculative verify pass uses:
    candidate ``j`` of slot ``i`` lands at absolute position
    ``length[i] + j``.

    k_new/v_new: (B, Hkv, S, D); ``write_mask`` (B, S) selects which
    (slot, candidate) writes are real — everything else (free slots,
    candidates past a slot's token budget) is redirected to the trash
    page, exactly the single-token function's non-live discipline.  The
    engine pre-reserves pages covering every maskable position, so real
    writes always land in pages the slot privately owns; trash-page
    collisions across slots are benign (the trash row is never read).
    """
    B, _, S, _ = k_new.shape
    bs = pool_k_l.shape[2]
    nb = block_table.shape[1]
    pos = length[:, None] + jnp.arange(S)[None, :]            # (B, S)
    col = jnp.clip(pos // bs, 0, nb - 1)
    page = jnp.where(write_mask > 0,
                     jnp.take_along_axis(block_table, col, axis=1), trash)
    off = pos % bs
    k_vals = k_new.transpose(0, 2, 1, 3).astype(pool_k_l.dtype)  # (B,S,Hkv,D)
    v_vals = v_new.transpose(0, 2, 1, 3).astype(pool_v_l.dtype)
    return (pool_k_l.at[page, :, off].set(k_vals),
            pool_v_l.at[page, :, off].set(v_vals))


def update_layer_cache_multi(
    k_cache: jax.Array, v_cache: jax.Array, k_new: jax.Array,
    v_new: jax.Array, length: jax.Array, write_mask: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Masked multi-position insert into a per-slot contiguous cache —
    the contiguous twin of :func:`append_tokens_paged` for the mixed
    (``kv_layout=auto``) speculative verify pass.

    k_new/v_new: (B, Hkv, S, D) writing positions ``length[i] + j``;
    ``write_mask`` (B, S) — masked-off positions are DROPPED (their
    index is pushed out of bounds and the scatter uses ``mode="drop"``),
    not clamped: a clamped write near ``max_len`` would slide backward
    over committed positions, which is exactly the corruption a
    ``dynamic_update_slice`` would have silently performed here.
    """
    B, Hkv, T, D = k_cache.shape
    S = k_new.shape[2]
    pos = length[:, None] + jnp.arange(S)[None, :]            # (B, S)
    pos = jnp.where(write_mask > 0, pos, T)                   # T = dropped

    def upd(c, n, p):
        return c.at[:, p].set(n.astype(c.dtype), mode="drop")

    return (jax.vmap(upd)(k_cache, k_new, pos),
            jax.vmap(upd)(v_cache, v_new, pos))


def spec_verify_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, length: jax.Array,
    *, window: Optional[int] = None, scale: Optional[float] = None,
) -> jax.Array:
    """Multi-query decode attention for the speculative verify pass.

    q: (B, Hq, S, D) where row ``j`` sits at absolute position
    ``length[i] + j`` (candidate ``j`` of slot ``i``); k/v_cache:
    (B, Hkv, T, D) with the span's own keys already written (the
    write-then-attend ordering of the paged decode step).  Row ``j``
    masks ``col <= length + j`` — with S=1 this is literally
    :func:`decode_attention`'s mask, and the grouped GQA layout + f32
    accumulators are identical, which is what keeps a verified token's
    logits equal to the sequential step's logits.
    """
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k_cache.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    qg = q.reshape(B, Hkv, group, S, D)
    s = jnp.einsum("bhgsd,bhtd->bhgst", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    row = length[:, None] + jnp.arange(S)[None, :]            # (B, S)
    col = jnp.arange(T)
    mask = col[None, None, :] <= row[:, :, None]              # (B, S, T)
    if window is not None:
        mask &= col[None, None, :] > row[:, :, None] - window
    s = jnp.where(mask[:, None, None], s, float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, S, D).astype(q.dtype)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, length: jax.Array,
    *, window: Optional[int] = None, scale: Optional[float] = None,
    ring_window: Optional[int] = None,
) -> jax.Array:
    """Single-position attention against a cache.

    q: (B, Hq, 1, D); k/v_cache: (B, Hkv, T, D); positions >= length are
    masked.  ``length`` is a scalar (lockstep decode) or a per-slot
    ``(B,)`` vector (continuous batching — each row masks against its own
    sequence length).  For ring caches the mask keeps every slot that has
    been written within the window (slot ages need no unrolling because
    the window fully covers the ring); ring mode is lockstep-only.
    """
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k_cache.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    # GQA-aware: NO jnp.repeat of the cache (a (B,Hq,T,D) materialization
    # that GSPMD must all-gather when Hq doesn't divide the model axis —
    # the 2x1GB gather the decode hillclimb eliminated), and the cache is
    # read in its stored dtype (f32 only in the accumulator).
    qg = q.reshape(B, Hkv, group, S, D)
    s = jnp.einsum("bhgsd,bhtd->bhgst", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    # NOTE (§Perf decode hillclimb): explicit sharding hints on q or on
    # the scores were both measured WORSE than leaving GSPMD to place
    # this einsum (1392MB vs 1116MB gathered per body) — refuted, so no
    # constraint here; the GQA reshape + dtype fix above is the keeper.
    s = jnp.where(_decode_mask(length, T, window=window, ring_window=ring_window),
                  s, float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, S, D).astype(q.dtype)


def _decode_mask(
    length: jax.Array, T: int, *, window: Optional[int],
    ring_window: Optional[int],
) -> jax.Array:
    """(1-or-B, 1, 1, 1, T) validity mask for single-position attention."""
    if is_per_slot(length):
        assert ring_window is None, "ring caches are lockstep-only"
        length = length[:, None, None, None, None]
    col = jnp.arange(T)[None, None, None, None, :]
    if ring_window is not None:
        written = jnp.minimum(length + 1, T)  # slots containing live data
        return col < written
    mask = col <= length  # include the token being decoded
    if window is not None:
        mask &= col > length - window
    return mask


def decode_attention_flat(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, length: jax.Array,
    *, window: Optional[int] = None, scale: Optional[float] = None,
    ring_window: Optional[int] = None,
) -> jax.Array:
    """GQA-materializing decode attention — the pre-hillclimb layout.

    Repeats K/V up to Hq heads before the score einsum.  Numerically it
    computes the same function as :func:`decode_attention`; kept as the
    alternative implementation on the serve engine's VPE axis so the
    controller has a real blind-offload candidate to trial (on some
    single-host shapes the flat layout vectorizes better; under GSPMD it
    is the variant the hillclimb rejected — either way the measurement,
    not the code, decides).
    """
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k_cache.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    k = jnp.repeat(k_cache, group, axis=1)
    v = jnp.repeat(v_cache, group, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = _decode_mask(length, T, window=window, ring_window=ring_window)
    s = jnp.where(mask.reshape(mask.shape[0], 1, 1, T), s, float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def decode_attention_pallas(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, length: jax.Array,
    *, window: Optional[int] = None, scale: Optional[float] = None,
    ring_window: Optional[int] = None,
) -> jax.Array:
    """Contiguous-layout resolution of the ``pallas`` decode variant.

    The Pallas kernel reads block-indirect *pages*; a contiguous
    (B, Hkv, T, D) slot cache has none, so this rung of the fallback
    ladder (docs/kernel_variants.md) delegates to the grouped path.
    The real kernel call lives in the paged step bodies
    (:func:`repro.models.transformer.decode_step_paged`), which skip
    the gather entirely when ``decode_impl == "pallas"``.
    """
    return decode_attention(q, k_cache, v_cache, length,
                            window=window, scale=scale,
                            ring_window=ring_window)


def _per_head_shard(kernel, q: jax.Array, pool_k_l: jax.Array,
                    pool_v_l: jax.Array, *tables: jax.Array) -> jax.Array:
    """Run a paged kernel on each tensor shard's own KV heads.

    GSPMD cannot partition a Mosaic kernel.  When the caller has set a
    mesh (``jax.set_mesh``) whose ``mp`` axis splits the heads, the call
    runs under ``shard_map``: ``q`` (B, Hq, C, D) and the pages
    (N, Hkv, bs, D) split on their head axis, block tables and lengths
    whole on every shard.  Each shard then attends its ``Hq/mp`` query
    heads against its ``Hkv/mp`` KV heads, which the serve engine only
    permits when ``Hkv % mp == 0`` (``sharding.kernel_shard_ok``).
    Without such a mesh the kernel sees every head.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or dict(mesh.shape).get("mp", 1) == 1:
        return kernel(q, pool_k_l, pool_v_l, *tables)
    heads = P(None, "mp", None, None)
    # check_vma=False: pallas_call's output shape carries no varying-axes
    # annotation; the out_specs above state it
    return jax.shard_map(
        kernel, in_specs=(heads, heads, heads) + (P(),) * len(tables),
        out_specs=heads, check_vma=False)(q, pool_k_l, pool_v_l, *tables)


def paged_decode_attention_kernel(
    q: jax.Array, pool_k_l: jax.Array, pool_v_l: jax.Array,
    block_table: jax.Array, length: jax.Array,
    *, window: Optional[int] = None, scale: Optional[float] = None,
    read_dtype=SLOT_CACHE_DTYPE,
) -> jax.Array:
    """Block-indirect decode attention — the ``pallas`` paged backend.

    Same contract as ``decode_attention(q, *paged_gather_layer(...))``
    but without ever linearizing the pages: the kernel DMAs pages
    straight from the pool via the scalar-prefetch block table.
    ``read_dtype`` defaults to the slot-cache dtype so the kernel scores
    exactly the values the gather path reads (token-parity contract).
    """
    from repro.kernels.paged_attention import paged_attention_pallas
    kernel = functools.partial(paged_attention_pallas, window=window,
                               scale=scale, read_dtype=read_dtype)
    return _per_head_shard(kernel, q, pool_k_l, pool_v_l, block_table,
                           length)


def paged_prefill_attention_kernel(
    q: jax.Array, pool_k_l: jax.Array, pool_v_l: jax.Array,
    block_table: jax.Array, base: jax.Array, chunk_len,
    *, window: Optional[int] = None, scale: Optional[float] = None,
) -> jax.Array:
    """Multi-query chunk attention over pages — the ``pallas`` prefill
    backend.  Requires the chunk's own K/V already written into its
    pages (write-then-attend ordering, see ``prefill_chunk_paged``);
    ``chunk_len`` may be a traced scalar — it becomes a scalar-prefetch
    operand, not a recompile."""
    from repro.kernels.paged_attention import paged_prefill_attention_pallas

    def kernel(q, pool_k_l, pool_v_l, block_table, base, chunk_len):
        return paged_prefill_attention_pallas(
            q, pool_k_l, pool_v_l, block_table, base,
            chunk_len=chunk_len, window=window, scale=scale)
    return _per_head_shard(kernel, q, pool_k_l, pool_v_l, block_table,
                           base, jnp.asarray(chunk_len, jnp.int32))


# Serve-engine VPE axis: decode-attention implementations (first = default).
# "pallas" resolves to the block-indirect kernel only on the paged data
# path; on contiguous caches it is an alias of "grouped" (fallback
# ladder, docs/kernel_variants.md).
DECODE_ATTN_VARIANTS = {
    "grouped": decode_attention,
    "flat": decode_attention_flat,
    "pallas": decode_attention_pallas,
}

# Variant names that are Pallas-kernel-backed (need the capability gate
# kernels/compat.pallas_supported + sharding.kernel_shard_ok to pass).
PAGED_KERNEL_IMPLS = ("pallas",)
