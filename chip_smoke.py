#!/usr/bin/env python3
"""Smoke run of the continuous-batching serve path on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four chips: the two mesh paths

One chip: qwen3-8b at its published widths (d_model 4096, 32 query and
8 KV heads of 128, d_ff 12288, vocab 151936, bfloat16) cut to 12 of its
36 layers so that weights (~7.1 GB) and the KV page pool fit one v5e's
16 GB.  Weights are random from a fixed seed.  Requests go through the
calls a user makes (``make_serve_engine``, ``submit``, ``run``) with
paged KV, the Pallas decode and chunked-prefill kernels pinned, a fixed
512-token prefill chunk and a fused decode horizon of 4.

Four chips (``--four-chips``), and nothing else:

* mesh (1, 4): all 36 layers, parameters and KV heads split over four
  chips (Hkv 8 / mp 4 = 2 KV heads per chip);
* mesh (4, 1): four replicas of the 12-layer cut behind one shared
  queue (``EngineReplicaGroup``), each on its own chip.

Every request is checked against a teacher-forced ``model.forward`` over
its prompt plus its generated tokens, on the same chip(s) with the same
parameters (:func:`check_against_reference`).  The script refuses to run
without a TPU and never falls back to the CPU.  Earlier lines report the
device, compile seconds, the number of compilations, a steady-state
tokens/s reading (a smoke reading, not a benchmark) and peak device
memory; on success the last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The persistent compilation cache follows ``repro.launch.compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` here.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Dict, List, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.distributed import sharding as sharding_lib  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.runtime.serve_loop import Request, make_serve_engine  # noqa: E402

SEED = 0
ONE_CHIP_LAYERS = 12

# How far below the reference's largest logit the engine's greedy token
# may score at the same position.  Both sides compute in bfloat16 with
# the same weights but in different orders: the engine reads K/V back
# from bf16 pages through the Pallas kernels, one chunk or one token at
# a time; the reference attends over the whole sequence at once.  Where
# the reference's two best logits lie closer than that ordering noise,
# the greedy pick can flip.  Readings on a TPU v5e, 12 layers, 800
# tokens: the largest gap was 0.0338, and at every disagreeing position
# it equalled the reference's own top-2 spacing (a near-tie); the
# Pallas and gather engines agree token for token until such a tie.
# The same reference on the host CPU backend differs from the chip's by
# at most 0.094 per logit (RMS 0.016), so a legitimate flip costs at
# most about 0.19; 0.25 covers that.  A wrong token (a broken cache,
# kernel or position) scores like a random vocabulary entry, several
# units below the maximum.  At smoke size a zeroed KV page or a block
# table one page off fails by 1.2-2.9 (tests/test_chip_smoke.py).
LOGIT_TOL = 0.25
# Share of generated tokens that must be the reference's own argmax.
# Ordering noise flips only near-ties: 0.965 of 800 tokens matched on
# the chip (12 layers).  A fault that perturbs every position a little
# flips many near-ties while staying under LOGIT_TOL; this floor
# catches that.
MIN_AGREEMENT = 0.75


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


@dataclasses.dataclass
class Traffic:
    """A fixed request mix: ``n`` prompts with lengths drawn uniformly
    from ``prompt_len`` and ``new_tokens`` drawn from the given choices."""
    n: int
    prompt_len: Tuple[int, int]
    new_tokens: Sequence[int]

    def requests(self, vocab: int, seed: int, rid0: int = 0) -> List[Request]:
        """Lengths and budgets follow ``seed`` alone, so every call with
        one seed has the same shapes; token ids also follow ``rid0``."""
        shapes = np.random.default_rng(seed)
        ids = np.random.default_rng([seed, rid0])
        lens = shapes.integers(self.prompt_len[0], self.prompt_len[1] + 1,
                               self.n)
        budgets = shapes.choice(self.new_tokens, self.n)
        return [Request(rid=rid0 + i,
                        prompt=ids.integers(0, vocab, int(n)).astype(np.int32),
                        max_new_tokens=int(b))
                for i, (n, b) in enumerate(zip(lens, budgets))]


# The fused decode horizon, and the traffic's budgets are tied to it:
# new_tokens = 1 + 4k, the first token comes from prefill and horizons of
# 4 then drain each budget exactly, so no single-step decode program is
# ever compiled for a ragged tail (which would also break the second
# pass's zero-compile reading).
DECODE_HORIZON = 4
ONE_CHIP_TRAFFIC = Traffic(8, (300, 1000), (33, 41, 49, 57))
# one prefill-chunk shape (two 512-token chunks per prompt): the four
# replicas of the (4, 1) mesh each compile every program for their own
# device, so fewer shapes keep that phase's compile bounded
FOUR_CHIP_TRAFFIC = Traffic(8, (769, 1024), (33, 41, 49, 57))


class CompileCounter:
    """Counts XLA backend compilations and their seconds while open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0

    def _on(self, event: str, duration: float, **_: object) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc: object) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


@functools.partial(jax.jit, static_argnums=0)
def _reference_scores(cfg: ModelConfig, params, tokens, targets):
    """Teacher-forced forward: per position, the two largest logits, the
    index of the largest, and the logit of ``targets`` (the token that
    followed in the served run).

    Every value comes out of one ``top_k``, never a separate ``max``: on
    the TPU, XLA fused a ``jnp.max`` into the logits matmul and returned
    an unrounded float32 maximum beside bf16-rounded gathered logits, so
    about half the positions read as disagreeing by up to half a bf16
    step (argmax agreement 0.520 for what is 0.965)."""
    logits = model_lib.forward(cfg, params, {"tokens": tokens})[0]
    top2, idx = jax.lax.top_k(logits, 2)
    chosen = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return top2, idx[:, 0], chosen


def check_against_reference(cfg: ModelConfig, params,
                            reqs: Sequence[Request], pad_to: int) -> Dict:
    """Hold every generated token to the reference's logits.

    Each request's prompt plus generated tokens runs through the model's
    plain forward pass, right-padded to ``pad_to`` so that one program
    serves every request (causal attention keeps the padding out of
    every real position).  At each generated position the engine's
    token must score within :data:`LOGIT_TOL` of that position's
    largest reference logit, and at least :data:`MIN_AGREEMENT` of all
    generated tokens must be the reference's argmax (by index).

    Besides the verdict it reports how sharp the reference is: the
    median gap between its two largest logits, and the share of
    positions where that gap is within the tolerance (there the check
    admits more than one token)."""
    worst, exact, total = 0.0, 0, 0
    spacings = []
    for r in reqs:
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.out, np.int32)])
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :len(seq)] = seq
        targets = np.zeros((pad_to,), np.int32)
        targets[:len(seq) - 1] = seq[1:]
        top2, best, chosen = (np.asarray(a) for a in _reference_scores(
            cfg, params, jnp.asarray(toks), jnp.asarray(targets)))
        gen = slice(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.out))
        top2, best, chosen = top2[gen], best[gen], chosen[gen]
        if not (np.all(np.isfinite(top2)) and np.all(np.isfinite(chosen))):
            raise SmokeFailure(f"request {r.rid}: non-finite reference logits")
        hit = best == targets[gen]
        gap = np.where(hit, 0.0, top2[:, 0] - chosen)
        spacings.append(top2[:, 0] - top2[:, 1])
        worst = max(worst, float(gap.max()))
        exact += int(hit.sum())
        total += len(gap)
        if gap.max() > LOGIT_TOL:
            i = int(np.argmax(gap))
            raise SmokeFailure(
                f"request {r.rid}: generated token {i} scores {gap[i]:.4f} "
                f"below the reference maximum (tolerance {LOGIT_TOL})")
    agreement = exact / max(total, 1)
    if agreement < MIN_AGREEMENT:
        raise SmokeFailure(
            f"only {agreement:.3f} of {total} generated tokens are the "
            f"reference's argmax (floor {MIN_AGREEMENT})")
    spacing = np.concatenate(spacings)
    return {"checked_tokens": total, "max_logit_gap": worst,
            "argmax_agreement": agreement,
            "top2_spacing_median": float(np.median(spacing)),
            "near_tie_share": float(np.mean(spacing <= LOGIT_TOL))}


def _serve(engine, reqs: Sequence[Request]) -> float:
    """Submit, drain, fence; returns the wall seconds of the pass."""
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.run()
    engine.check_kv()
    return time.perf_counter() - t0


def serve_phase(cfg: ModelConfig, params, *, mesh_shape=(1, 1),
                traffic: Traffic = ONE_CHIP_TRAFFIC, slots: int = 8,
                max_len: int = 2048, prefill_chunk: int = 512,
                block_size: int = 16) -> Dict:
    """One serving phase: build the engine, serve the traffic twice (the
    first pass compiles, the second is the steady reading), check both
    passes against the reference and return what the script reports.
    Raises :class:`SmokeFailure` on any wrong or missing result."""
    engine = make_serve_engine(
        cfg, params, mesh_shape=mesh_shape, slots=slots, max_len=max_len,
        kv_layout="paged", block_size=block_size,
        prefill_chunk=prefill_chunk, decode_horizon=DECODE_HORIZON,
        decode_impl="pallas", prefill_kernel="pallas")
    replicas = getattr(engine, "engines", [engine])
    if not all(e._pallas_ok for e in replicas):
        raise SmokeFailure("the Pallas kernels are gated off on this engine")
    # same shapes in both passes, so the second compiles nothing
    warm = traffic.requests(cfg.vocab_size, SEED)
    steady = traffic.requests(cfg.vocab_size, SEED, rid0=len(warm))
    with CompileCounter() as warm_compiles:
        warm_s = _serve(engine, warm)
    with CompileCounter() as steady_compiles:
        steady_s = _serve(engine, steady)
    for r in warm + steady:
        if r.status != "done" or len(r.out) != r.max_new_tokens:
            raise SmokeFailure(
                f"request {r.rid}: status {r.status!r}, "
                f"{len(r.out)}/{r.max_new_tokens} tokens ({r.error_detail})")
    check = check_against_reference(cfg, params, warm + steady, max_len)
    served = [len(e.completed) for e in replicas]
    return {
        "layers": cfg.num_layers,
        "mesh": tuple(mesh_shape),
        "requests": len(warm) + len(steady),
        "served_per_replica": served,
        "compile_s": warm_compiles.seconds,
        "compiles": warm_compiles.count,
        "steady_compiles": steady_compiles.count,
        "warm_pass_s": warm_s,
        "steady_tok_per_s": sum(len(r.out) for r in steady) / steady_s,
        **check,
    }


def param_bytes_per_device(params) -> Dict[str, int]:
    """Bytes of parameter shards resident on each device."""
    out: Dict[str, int] = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def peak_bytes(devices) -> List[object]:
    """``peak_bytes_in_use`` per device, or "not reported"."""
    peaks: List[object] = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", "not reported"))
    return peaks


def _report(name: str, r: Dict) -> None:
    print(f"[{name}] layers {r['layers']}, mesh {r['mesh']}: "
          f"{r['requests']} requests served "
          f"(per replica {r['served_per_replica']})")
    print(f"[{name}] compile {r['compile_s']:.1f} s over "
          f"{r['compiles']} compilations in the first pass; "
          f"{r['steady_compiles']} in the second")
    print(f"[{name}] smoke reading, not a benchmark: "
          f"{r['steady_tok_per_s']:.1f} completed tokens/s in the second "
          f"pass (first pass with compiles {r['warm_pass_s']:.1f} s)")
    print(f"[{name}] teacher-forced check passed: {r['checked_tokens']} "
          f"generated tokens, max logit gap {r['max_logit_gap']:.4f} "
          f"(tolerance {LOGIT_TOL}), argmax agreement "
          f"{r['argmax_agreement']:.3f}; reference top-2 spacing median "
          f"{r['top2_spacing_median']:.4f}, within tolerance at "
          f"{r['near_tie_share']:.3f} of positions")


def qwen3_8b(layers: int) -> ModelConfig:
    return dataclasses.replace(get_config("qwen3-8b"), num_layers=layers)


def one_chip() -> None:
    cfg = qwen3_8b(ONE_CHIP_LAYERS)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(SEED))
    print(f"[one chip] parameters: {param_bytes_per_device(params)} bytes "
          f"per device id")
    _report("one chip", serve_phase(cfg, params))


def four_chips() -> None:
    full = get_config("qwen3-8b")
    mesh = sharding_lib.serve_mesh(1, 4)
    params = model_lib.init_params(full, jax.random.PRNGKey(SEED), mesh=mesh)
    per_dev = param_bytes_per_device(params)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    print(f"[mesh 1x4] parameters: {total} bytes in all, {per_dev} bytes "
          f"per device id")
    if len(per_dev) != 4 or max(per_dev.values()) >= total:
        raise SmokeFailure("a device holds the whole 36-layer model")
    _report("mesh 1x4", serve_phase(full, params, mesh_shape=(1, 4),
                                    traffic=FOUR_CHIP_TRAFFIC))
    del params
    cfg = qwen3_8b(ONE_CHIP_LAYERS)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(SEED))
    r = serve_phase(cfg, params, mesh_shape=(4, 1), traffic=FOUR_CHIP_TRAFFIC)
    if min(r["served_per_replica"]) == 0:
        raise SmokeFailure(f"a replica served nothing: "
                           f"{r['served_per_replica']}")
    _report("mesh 4x1", r)


def main(argv: Sequence[str] = ()) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1,4) and (4,1) mesh paths")
    args = ap.parse_args(list(argv))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); refusing "
              f"to run", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}")
    print(f"compilation cache: {enable_compile_cache()}")
    try:
        (four_chips if args.four_chips else one_chip)()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"peak_bytes_in_use per device: {peak_bytes(devices[:need])}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
