"""Model facade: family dispatch + abstract parameter/cache specs.

Every family exposes the same functional surface:

    init_params(cfg, rng[, mesh])        -> params pytree (placed)
    param_specs(cfg)                     -> ShapeDtypeStruct pytree (no alloc)
    forward(cfg, params, batch)          -> logits (B, S, V) f32
    loss_fn(cfg, params, batch)          -> scalar CE loss
    init_cache / cache_specs             -> serving cache
    prefill(cfg, params, batch, cache)   -> (cache, last logits)
    decode_step(cfg, params, cache, tok) -> (cache, logits)

``batch`` is a dict: {"tokens", "labels"} (+ "frames" for encdec — the
stubbed modality frontend output, per the brief).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.distributed import sharding

from . import encdec, hybrid, kvcache, ssm_stack, transformer

_FAMILIES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,   # chameleon: early-fusion = ordinary token ids
    "hybrid": hybrid,
    "ssm": ssm_stack,
    "encdec": encdec,
}


def family_module(cfg: ModelConfig):
    return _FAMILIES[cfg.family]


# -- params ------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def init_params(cfg: ModelConfig, rng, mesh: Optional[Mesh] = None) -> Any:
    """Parameters in their final dtype and placement, made by one jit.

    Inside the jit each float32 draw fuses with its cast, so no float32
    copy of a weight reaches device memory.  With ``mesh`` every leaf is
    made under its :func:`~repro.distributed.sharding.param_specs`
    sharding, so a device creates only its own shard; without one the
    parameters land on the default device.
    """
    params = family_module(cfg).init_params(cfg, rng)
    if mesh is not None:
        params = jax.lax.with_sharding_constraint(
            params, sharding.param_shardings(params, mesh))
    return params


def param_specs(cfg: ModelConfig) -> Any:
    """Abstract params via eval_shape — zero allocation, dtype-faithful."""
    rng_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(
        lambda k: family_module(cfg).init_params(cfg, k), rng_spec)


def count_params_from_shapes(cfg: ModelConfig, active_only: bool = False) -> int:
    specs = param_specs(cfg)
    total = 0
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    for path, leaf in flat:
        n = 1
        for d in leaf.shape:
            n *= d
        key = jax.tree_util.keystr(path)
        if active_only and "experts_" in key:
            n = int(n * cfg.top_k / max(cfg.num_experts, 1))
        total += n
    return total


# -- training ------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: Any, batch: Dict[str, jax.Array]) -> jax.Array:
    if cfg.family == "encdec":
        return encdec.forward(cfg, params, batch["frames"], batch["tokens"])
    return family_module(cfg).forward(cfg, params, batch["tokens"])


def loss_fn(cfg: ModelConfig, params: Any, batch: Dict[str, jax.Array]) -> jax.Array:
    """Mean next-token cross-entropy (labels = tokens shifted by caller)."""
    logits = forward(cfg, params, batch)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# -- serving ------------------------------------------------------------------------

def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    return family_module(cfg).cache_specs(cfg, batch, max_len)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    return family_module(cfg).init_cache(cfg, batch, max_len)


def decode_step(cfg: ModelConfig, params: Any, cache: Any, tokens: jax.Array
                ) -> Tuple[Any, jax.Array]:
    return family_module(cfg).decode_step(cfg, params, cache, tokens)


# -- continuous-batching slot serving (transformer families only) -----------------

_SLOT_FAMILIES = ("dense", "moe", "vlm")


def supports_slot_serving(cfg: ModelConfig) -> bool:
    """Slot-recycled continuous batching needs a positional KV cache;
    recurrent/hybrid/encdec families keep lockstep ``ServeLoop``."""
    return cfg.family in _SLOT_FAMILIES


def _slot_module(cfg: ModelConfig):
    if not supports_slot_serving(cfg):
        raise ValueError(
            f"continuous batching unsupported for family {cfg.family!r} "
            f"(supported: {_SLOT_FAMILIES})")
    return family_module(cfg)


def init_slot_cache(cfg: ModelConfig, slots: int, max_len: int) -> Any:
    return _slot_module(cfg).init_slot_cache(cfg, slots, max_len)


def prefill_slot_kv(cfg: ModelConfig, params: Any, tokens: jax.Array,
                    true_len: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return _slot_module(cfg).prefill_slot_kv(cfg, params, tokens, true_len)


def insert_slot_kv(cache: Any, k_new: jax.Array, v_new: jax.Array,
                   slot: jax.Array, true_len: jax.Array) -> Any:
    return kvcache.insert_slot_kv(cache, k_new, v_new, slot, true_len)


def insert_slot_kv_at(cache: Any, k_new: jax.Array, v_new: jax.Array,
                      slot: jax.Array, start_pos: jax.Array,
                      true_len: jax.Array) -> Any:
    return kvcache.insert_slot_kv_at(cache, k_new, v_new, slot, start_pos, true_len)


def prefill_suffix_kv(cfg: ModelConfig, params: Any, tokens: jax.Array,
                      prefix_k: jax.Array, prefix_v: jax.Array,
                      prefix_len: jax.Array, true_len: jax.Array
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Suffix-only prefill against cached prefix KV pages (prefix cache)."""
    return _slot_module(cfg).prefill_suffix_kv(
        cfg, params, tokens, prefix_k, prefix_v, prefix_len, true_len)


def decode_step_slots(cfg: ModelConfig, params: Any, cache: Any,
                      tokens: jax.Array, decode_impl: str = "grouped"
                      ) -> Tuple[Any, jax.Array]:
    return _slot_module(cfg).decode_step(cfg, params, cache, tokens,
                                         decode_impl=decode_impl)


# -- paged (block-indirect) KV layout ----------------------------------------

def init_page_pool(cfg: ModelConfig, num_pages: int, block_size: int) -> Any:
    return _slot_module(cfg).init_page_pool(cfg, num_pages, block_size)


def init_paged_cache(cfg: ModelConfig, slots: int, max_len: int,
                     block_size: int, trash: int) -> Any:
    return _slot_module(cfg).init_paged_cache(cfg, slots, max_len,
                                              block_size, trash)


def decode_step_paged(cfg: ModelConfig, params: Any, pool: Any, cache: Any,
                      tokens: jax.Array, live: jax.Array,
                      decode_impl: str = "grouped"
                      ) -> Tuple[Any, Any, jax.Array]:
    return _slot_module(cfg).decode_step_paged(cfg, params, pool, cache,
                                               tokens, live,
                                               decode_impl=decode_impl)


def prefill_chunk_paged(cfg: ModelConfig, params: Any, pool: Any,
                        bt_row: jax.Array, tokens: jax.Array,
                        base: jax.Array, chunk_len: jax.Array,
                        kernel: str = "gather"
                        ) -> Tuple[Any, jax.Array]:
    """One prompt chunk prefilled directly over the paged KV layout
    (reads prior pages through the block table, writes its own).
    ``kernel``: ``"gather"`` linearizes pages in-jit; ``"pallas"``
    scores them in place via the block-indirect multi-query kernel
    (the serve engine's ``prefill_kernel`` axis)."""
    return _slot_module(cfg).prefill_chunk_paged(
        cfg, params, pool, bt_row, tokens, base, chunk_len, kernel=kernel)


def decode_step_mixed(cfg: ModelConfig, params: Any, cache: Any, pool: Any,
                      tokens: jax.Array, use_paged: jax.Array,
                      live: jax.Array, decode_impl: str = "grouped"
                      ) -> Tuple[Any, Any, jax.Array]:
    return _slot_module(cfg).decode_step_mixed(cfg, params, cache, pool,
                                               tokens, use_paged, live,
                                               decode_impl=decode_impl)


# -- fused multi-token decode horizons ----------------------------------------

def decode_steps_slots(cfg: ModelConfig, params: Any, cache: Any,
                       tokens: jax.Array, live: jax.Array, eos_ids: jax.Array,
                       budget: jax.Array, horizon: int,
                       decode_impl: str = "grouped"
                       ) -> Tuple[Any, jax.Array, jax.Array, jax.Array]:
    """Fused H decode steps (contiguous layout): one on-device scan with
    in-graph greedy feedback and stop handling; one host fence per H
    tokens instead of per token."""
    return _slot_module(cfg).decode_steps_slots(
        cfg, params, cache, tokens, live, eos_ids, budget, horizon,
        decode_impl=decode_impl)


def decode_steps_paged(cfg: ModelConfig, params: Any, pool: Any, cache: Any,
                       tokens: jax.Array, live: jax.Array, eos_ids: jax.Array,
                       budget: jax.Array, horizon: int,
                       decode_impl: str = "grouped"
                       ) -> Tuple[Any, Any, jax.Array, jax.Array, jax.Array]:
    """Fused H decode steps over the paged layout (pages covering the
    whole horizon must be pre-reserved in the block tables)."""
    return _slot_module(cfg).decode_steps_paged(
        cfg, params, pool, cache, tokens, live, eos_ids, budget, horizon,
        decode_impl=decode_impl)


def decode_steps_mixed(cfg: ModelConfig, params: Any, cache: Any, pool: Any,
                       tokens: jax.Array, use_paged: jax.Array,
                       live: jax.Array, eos_ids: jax.Array, budget: jax.Array,
                       horizon: int, decode_impl: str = "grouped"
                       ) -> Tuple[Any, Any, jax.Array, jax.Array, jax.Array]:
    """Fused H decode steps for ``kv_layout=auto``."""
    return _slot_module(cfg).decode_steps_mixed(
        cfg, params, cache, pool, tokens, use_paged, live, eos_ids, budget,
        horizon, decode_impl=decode_impl)


# -- speculative decoding (one-pass draft verification) -----------------------

def spec_verify_paged(cfg: ModelConfig, params: Any, pool: Any, cache: Any,
                      tokens: jax.Array, live: jax.Array, eos_ids: jax.Array,
                      budget: jax.Array
                      ) -> Tuple[Any, Any, jax.Array, jax.Array, jax.Array]:
    """Score an S-token candidate span per slot in one pass over the
    paged layout and commit the longest verified prefix + one correction
    token (same return contract as :func:`decode_steps_paged`)."""
    return _slot_module(cfg).spec_verify_paged(
        cfg, params, pool, cache, tokens, live, eos_ids, budget)


def spec_verify_mixed(cfg: ModelConfig, params: Any, cache: Any, pool: Any,
                      tokens: jax.Array, use_paged: jax.Array,
                      live: jax.Array, eos_ids: jax.Array, budget: jax.Array
                      ) -> Tuple[Any, Any, jax.Array, jax.Array, jax.Array]:
    """Speculative verify for ``kv_layout=auto``."""
    return _slot_module(cfg).spec_verify_mixed(
        cfg, params, cache, pool, tokens, use_paged, live, eos_ids, budget)


def prefill(cfg: ModelConfig, params: Any, batch: Dict[str, jax.Array], cache: Any
            ) -> Tuple[Any, jax.Array]:
    """Prompt processing.  Families without a fused prefill path replay
    the train-mode forward and then enter decode (correct, slower)."""
    mod = family_module(cfg)
    if hasattr(mod, "prefill"):
        return mod.prefill(cfg, params, batch["tokens"], cache)
    if cfg.family == "encdec":
        enc_out = encdec.encode(cfg, params, batch["frames"])
        xk, xv = encdec.precompute_cross_kv(cfg, params, enc_out)
        cache = {**cache, "xk": xk, "xv": xv}
        logits = encdec.decode_train(cfg, params, batch["tokens"], enc_out)
        return cache, logits[:, -1:, :]
    # recurrent families: replay tokens through decode steps via scan
    tokens = batch["tokens"]

    def step(cache, tok):
        cache, logits = decode_step(cfg, params, cache, tok[:, None])
        return cache, logits

    cache, logits = jax.lax.scan(step, cache, jnp.moveaxis(tokens, 1, 0))
    return cache, logits[-1]
