"""Operations and bytes that the algorithm needs, from shapes alone.

These count what the mathematics requires, not what today's kernels
happen to do: a decode kernel that DMAs every page of a slot's block
table still needs only the live pages, so when the kernel is bounded
later its roofline share rises and this count stays right.  Nothing
padded is counted: no padded chunk rows, no frozen or free slots, no
dead pages.

``dims`` is a reference's :func:`dims` dict (L, d, Hq, Hkv, D, F, V) and
``kv_bytes`` the size of one stored K or V element.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple


def pages(n_keys: int, block: int) -> int:
    return -(-n_keys // block)


def decode_attention(n_keys: Sequence[int], dims: Dict, block: int,
                     kv_bytes: int = 2) -> Tuple[float, float]:
    """One layer's decode attention for a batch of live rows, row ``i``
    attending to ``n_keys[i]`` keys: (flops, bytes).  FLOPs: q.k and
    p.v, two per multiply-add.  Bytes: the live pages of K and V, and
    the query read and the output written."""
    Hq, Hkv, D = dims["Hq"], dims["Hkv"], dims["D"]
    flops = sum(4.0 * Hq * D * n for n in n_keys)
    kv = sum(2.0 * Hkv * D * pages(n, block) * block * kv_bytes
             for n in n_keys)
    return flops, kv + len(n_keys) * 2.0 * Hq * D * kv_bytes


def prefill_attention(base: int, length: int, dims: Dict, block: int,
                      kv_bytes: int = 2) -> Tuple[float, float]:
    """One layer's attention for a prompt chunk of ``length`` queries at
    positions ``base .. base + length - 1``, each attending causally to
    every key up to its own position: (flops, bytes)."""
    Hq, Hkv, D = dims["Hq"], dims["Hkv"], dims["D"]
    keys = length * base + length * (length + 1) / 2.0
    flops = 4.0 * Hq * D * keys
    kv = 2.0 * Hkv * D * pages(base + length, block) * block * kv_bytes
    return flops, kv + 2.0 * Hq * length * D * kv_bytes


def layer_matmul_flops(dims: Dict) -> float:
    """Projection and feed-forward FLOPs of one token in one layer."""
    d, Hq, Hkv, D, F = (dims[k] for k in ("d", "Hq", "Hkv", "D", "F"))
    params = d * Hq * D + 2 * d * Hkv * D + Hq * D * d + 3 * d * F
    return 2.0 * params


def head_flops(dims: Dict) -> float:
    """The output head for one position."""
    return 2.0 * dims["d"] * dims["V"]


def token_flops(n_keys: int, dims: Dict) -> float:
    """Every layer for one token attending to ``n_keys`` keys (no head)."""
    return dims["L"] * (layer_matmul_flops(dims) + 4.0 * dims["Hq"]
                        * dims["D"] * n_keys)


def chunk_flops(base: int, length: int, dims: Dict) -> float:
    """Every layer for a prompt chunk (no head)."""
    keys = length * base + length * (length + 1) / 2.0
    return dims["L"] * (length * layer_matmul_flops(dims)
                        + 4.0 * dims["Hq"] * dims["D"] * keys)
