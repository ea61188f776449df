# Wave vs continuous batching + prefix-cache TTFT + paged admission +
# chunked-prefill interference + fused decode horizons. CSV+JSON.
"""Serving benchmark: wave vs continuous batching, prefix-cache TTFT,
paged-vs-contiguous admission cost, chunked-prefill decode
interference, fused decode horizons, and priority-mix QoS under page
pressure.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.serve_bench [--fast]

(part 7's mesh sweep serves at mp 1, 2 and 4 in this one process and
needs at least four devices; on the CPU the flag above provides them)

Part 10 — fault-storm sweep (what PR 10's recovery ladder buys): the
same mixed interactive/batch workload served twice on identically
configured engines (paged KV, chunked prefill, fused horizon 4,
speculation 4, watchdog armed) — once clean, once under a scripted
fault storm that walks the whole degradation ladder: speculative
verify poisoned then device-faulted (spec -> off), the fused horizon
call NaN'd then stalled (horizon -> 1), the single-token incumbent
device-faulted, a prefill chunk device-faulted, and a page allocation
faulted, with probation re-promoting each demoted rung once its
window runs clean.  The engine must absorb every injected fault
without raising, finish every request at exact greedy parity with the
clean arm, and drain leak-free.  Reported: tok/s retention
(storm/clean), interactive TTFT p95 on both arms, and the full
recovery ledger (device/numeric faults, watchdog trips, demotions by
rung, re-promotions, failures by reason).

Part 9 — speculative decoding sweep (what PR 9's draft-and-verify
buys, and the regime where it must refuse to pay): a repetitive
decode-bound workload (two shared prompt templates, long generations —
the n-gram proposer's home turf) and an adversarial zero-repetition
workload (fresh unique prompts every pass, so drafts essentially never
land), each served with spec_draft off / pinned 4 / auto over a pinned
16-step fused horizon.  On the repetitive workload the best
speculative arm must clear >= 1.3x aggregate tok/s over the plain
fused-horizon engine (the best non-speculative fixed choice from
part 5) at exact greedy parity; on the adversarial workload the
measured accept rate collapses and auto must back off to "off"
(recorded per bucket) rather than keep paying the wide verify pass.
Auto lands within 10% of the best arm on BOTH workloads; every arm
drains leak-free (cross-structure page audit).

Part 8 — kernel backend sweep (what PR 8's measured variants buy): the
paged engine served with both kernel axes pinned to gather, pinned to
pallas, and measured (auto), on a decode-bound and a prefill-heavy
workload.  Token-exact greedy parity across backends and leak-free
drains are the pass criteria; per-bucket auto selections for
serve_decode_impl AND prefill_kernel are recorded.  On CPU the pallas
arm runs interpreted, so auto converging away from it is the dispatch
loop doing its job — the TPU re-run is the real gather-vs-indirect-DMA
measurement (ROADMAP).

Part 6 — priority classes under over-pressure (what PR 6's scheduling
buys): a deep burst of short interactive turns mixed with long batch
generations through a page pool sized FAR below worst case
(page_budget), run twice on identical engines — once with every
request submitted as plain FIFO batch traffic, once with the real
priority classes (+ swap-enabled preemption).  The class-aware
scheduler admits interactive requests first and may preempt batch
residencies for their pages, so interactive TTFT p50/p95 and SLO
attainment (share of interactive requests under the FIFO arm's median
TTFT) must be strictly better than FIFO *at equal aggregate tok/s*
(within 15% — the preempted work is swapped, not recomputed) and at
exact greedy parity between the arms.  Per-class latencies, attainment
and preemption/swap counts are appended to BENCH_serve.json.

Part 5 — fused decode horizons (what amortizing per-token dispatch
buys, and what it costs under load): a decode-bound workload (short
prompts, long generations) swept over decode_horizon 1/4/16/auto, and
an admission-pressure workload (part 1's bimodal mix, queue always
deep) over the same sweep.  A long horizon fuses H decode steps into
one on-device loop — one host fence per H tokens — so steady-state
aggregate tok/s must improve >= 1.5x at the best fixed horizon on the
decode-bound workload; under pressure the fused call delays admissions
and burns frozen steps on short-budget slots, so the best fixed choice
shrinks.  ``auto`` (the VPE axis, per-token wall per queue-depth ×
occupancy bucket) must land within 10% of the best fixed choice on
BOTH workloads, and its per-bucket selections are recorded as the
back-off evidence.  Exact greedy parity across every horizon is part
of the pass criterion.  Appended to BENCH_serve.json like every other
record.

Part 4 — mixed workload under long-prompt load (what chunked prefill
exists for): one 2k-token prompt arrives amid short-prompt decode
traffic.  Monolithic (whole-chunk) prefill stalls every decoding slot
for the long prompt's entire prefill and holds the admission queue
behind it; 128-token chunks interleave with decode steps, so decode
service interruption per engine step (``stats.decode_stall_s``) is
bounded by one chunk and the short prompts behind the long one start
immediately.  Reported: decode-stall p95 and the TTFT p95 of the short
prompts submitted after the long one, chunked vs monolithic, at exact
greedy parity.  Appended to BENCH_serve.json like every other record.
(2k, not the 8k+ regime chunking ultimately targets: the MONOLITHIC
comparator materializes its full S x T attention scores on the CPU
reference path — ~2 GB at 8k — while the chunked side is bounded at
chunk x T; the stall ratio only grows with prompt length, so 2k is the
conservative end of the claim.)

Part 3 — long-shared-prefix admission (the paged layout's raison
d'être): a cached system prompt of 1k..8k tokens, warm admissions with
a short tail.  The contiguous layout must gather the matched pages and
COPY them into the slot's KV region — O(matched length); the paged
layout aliases them into the slot's block table — O(1).  The engine's
``kv_place_s`` stat isolates exactly that placement span, so the
benchmark's pass criterion is the asymptotic *shape*: flat for paged
across 1k->8k matched tokens, growing for contiguous.  Appended to
BENCH_serve.json like every other record (the trajectory accumulates).

Part 1 — wave vs continuous: mixed prompt lengths (4..24) and strongly
mixed output lengths (short interactive turns interleaved with long
generations).  Wave batching decodes every slot until the wave's longest
request and holds the queue until the wave finishes; the continuous
engine retires each sequence at its own length and refills the freed
slot mid-decode.  Aggregate tokens/s = useful generated tokens /
(prefill + decode) wall.

Part 2 — shared-system-prompt workload: every request shares a long
prefix (the production shape: one system prompt, many users).  The same
engine runs it with the radix-tree prefix cache off and on; with the
cache, admission copies the cached prefix pages into the slot and
prefills only the short tail, which must cut TTFT by >= 2x at exact
greedy parity.  Results are emitted as one machine-readable JSON line
(tok/s, TTFT p50/p95, hit rate) and written to BENCH_serve.json so the
bench trajectory accumulates across PRs.

Both paths are warmed (jit compiles + VPE tuning excluded from the
timed run).
"""

from __future__ import annotations

import copy
import json
import os
import sys
import time
from typing import List

import jax
import numpy as np

from repro.configs import get_config
from repro.core import VPE
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model
from repro.runtime.serve_faults import FaultPlan, FaultSpec
from repro.runtime.serve_loop import (
    SERVE_AXES, ContinuousBatchingEngine, Request, ServeLoop, WaveScheduler,
    make_serve_engine)

SLOTS = 4
MAX_LEN = 96
PREFIX_MAX_LEN = 512
PREFIX_LEN = 384         # shared system prompt (24 KV blocks of 16)
BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")

# record envelope (schema v1): every line in BENCH_serve.json is
# {"bench": <part name>, "schema": 1, "pr": <PR that produced it>,
#  "metrics": {...}} — one shape for every part, so external trajectory
# tooling can read the whole file without per-part key knowledge.  Bump
# SCHEMA on envelope changes, PR per growth session.
SCHEMA = 1
PR = 10


def append_record(bench: str, metrics: dict, *, pr: int = PR) -> None:
    """THE writer: every part appends through here, so records cannot
    drift back to ad-hoc top-level keys.  Prints the line and appends it
    to BENCH_JSON (the trajectory accumulates across PRs)."""
    record = {"bench": bench, "schema": SCHEMA, "pr": pr, "metrics": metrics}
    line = json.dumps(record, sort_keys=True)
    print(line)
    with open(BENCH_JSON, "a") as f:
        f.write(line + "\n")


def make_workload(rng, n: int, vocab: int) -> List[Request]:
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 25))
        # heavy skew: 2/3 short turns, 1/3 long generations — wave
        # batching decodes EVERY slot to the wave's longest request
        new = 4 if i % 3 else 64
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=new))
    return reqs


def useful_tokens(reqs: List[Request]) -> int:
    return sum(r.max_new_tokens for r in reqs)


def run_wave(sched: WaveScheduler, reqs: List[Request]) -> float:
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    sched.run()
    wall = time.perf_counter() - t0
    return useful_tokens(reqs) / wall


def run_continuous(eng: ContinuousBatchingEngine, reqs: List[Request]) -> float:
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    print(f"# continuous stats: {eng.stats.summary()}")
    return useful_tokens(reqs) / wall


def make_shared_prefix_workload(rng, n: int, vocab: int) -> List[Request]:
    """One shared system prompt, per-request tails: the warm-serving shape."""
    shared = rng.integers(0, vocab, PREFIX_LEN).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, vocab, int(rng.integers(4, 9))).astype(np.int32)
        reqs.append(Request(rid=i, prompt=np.concatenate([shared, tail]),
                            max_new_tokens=4))
    return reqs


def percentile(xs: List[float], p: float) -> float:
    return float(np.percentile(np.asarray(xs), p)) if xs else 0.0


def run_engine(eng: ContinuousBatchingEngine, reqs: List[Request]) -> dict:
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    # parity outputs from THIS call's requests (eng.completed is
    # cumulative and would also contain the warm-up pass's rids)
    outs = {r.rid: list(map(int, r.out)) for r in reqs}
    return {
        "tok_per_s": useful_tokens(reqs) / wall,
        "ttft_p50_ms": percentile(eng.stats.ttft_s, 50) * 1e3,
        "ttft_p95_ms": percentile(eng.stats.ttft_s, 95) * 1e3,
        "hit_rate": eng.stats.prefix_hit_rate,
        "tokens_saved": eng.stats.prefix_tokens_saved,
        "outs": outs,
    }


def bench_prefix_cache(cfg, params, n_requests: int) -> bool:
    """Shared-prefix workload, cache off vs on; returns pass/fail."""
    rng = np.random.default_rng(1)
    reqs = make_shared_prefix_workload(rng, n_requests, cfg.vocab_size)

    def fresh_engine(blocks: int) -> ContinuousBatchingEngine:
        return ContinuousBatchingEngine(
            cfg, params, slots=SLOTS, max_len=PREFIX_MAX_LEN,
            prefix_blocks=blocks, block_size=16)

    off = fresh_engine(0)
    on = fresh_engine(64)
    # warm: compiles out of the timed pass; for the cached engine this is
    # also the paper's warm-up phase — the tree fills, later passes hit
    run_engine(off, copy.deepcopy(reqs))
    run_engine(on, copy.deepcopy(reqs))
    off.stats, on.stats = type(off.stats)(), type(on.stats)()

    r_off = run_engine(off, copy.deepcopy(reqs))
    r_on = run_engine(on, copy.deepcopy(reqs))
    parity = r_off.pop("outs") == r_on.pop("outs")
    speedup = (r_off["ttft_p50_ms"] / r_on["ttft_p50_ms"]
               if r_on["ttft_p50_ms"] else 0.0)
    append_record("serve_prefix_cache", {
        "n_requests": n_requests,
        "prefix_len": PREFIX_LEN,
        "cache_off": r_off,
        "cache_on": r_on,
        "ttft_p50_speedup": round(speedup, 2),
        "greedy_parity": parity,
    })
    ok = parity and speedup >= 2.0
    print(f"# prefix-cache ttft p50 speedup: {speedup:.2f}x, "
          f"hit rate {r_on['hit_rate']:.2f}, parity "
          f"{'exact' if parity else 'BROKEN'} "
          f"({'PASS' if ok else 'FAIL'}: need >=2x at exact parity)")
    return ok


# paged-admission bench: matched lengths swept at fixed tail length
ADMIT_MATCHED = [1024, 2048, 4096, 8192]
ADMIT_BLOCK = 64            # bigger pages at this scale: 8k = 128 pages
ADMIT_TAIL = 8
ADMIT_REPS = 8


def _seed_template(eng: ContinuousBatchingEngine, template: np.ndarray) -> None:
    """Install a template's full blocks into the engine's prefix tree
    WITHOUT serving it: an 8k cold prefill is quadratic in the prompt on
    the CPU reference path, and the admission measurement only needs the
    tree topology + device pages to exist (their values don't affect
    placement wall time)."""
    h = eng.prefix_cache.acquire(template)
    eng.prefix_cache.extend(h, template)
    eng.prefix_cache.release(h)


def bench_paged_admission(cfg, params) -> bool:
    """Warm-admission placement cost vs matched prefix length."""
    rng = np.random.default_rng(2)
    results: dict = {}
    for layout in ("contiguous", "paged"):
        for matched in ADMIT_MATCHED:
            template = rng.integers(0, cfg.vocab_size, matched).astype(np.int32)
            # slots are provisioned for the workload (prompt + headroom),
            # as a deployment would: the contiguous slot region — and
            # therefore its admission copy-in — scales with it, the
            # paged block table costs the same few hundred ids either way
            max_len = matched + 2 * ADMIT_BLOCK
            eng = ContinuousBatchingEngine(
                cfg, params, slots=1, max_len=max_len,
                prefix_blocks=matched // ADMIT_BLOCK + 4,
                block_size=ADMIT_BLOCK, kv_layout=layout)
            _seed_template(eng, template)

            def burst(n):
                for i in range(n):
                    tail = rng.integers(0, cfg.vocab_size,
                                        ADMIT_TAIL).astype(np.int32)
                    eng.submit(Request(rid=i, prompt=np.concatenate(
                        [template, tail]), max_new_tokens=1))
                eng.run()

            burst(2)                      # jit warm-up at this shape
            eng.stats = type(eng.stats)()
            burst(ADMIT_REPS)
            assert eng.stats.prefix_hits == ADMIT_REPS
            # min over reps: placement is deterministic work, so the
            # floor is the measurement and everything above it is
            # scheduler noise (medians wobble on a loaded host)
            place_us = float(np.min(eng.stats.kv_place_s) * 1e6)
            ttft_ms = percentile(eng.stats.ttft_s, 50) * 1e3
            results.setdefault(str(matched), {})[layout] = {
                "kv_place_us": round(place_us, 1),
                "ttft_p50_ms": round(ttft_ms, 2),
            }
            print(f"# admission {layout:>10} matched={matched:5d}: "
                  f"place {place_us:9.1f}us, ttft p50 {ttft_ms:7.2f}ms")
    lo, hi = str(ADMIT_MATCHED[0]), str(ADMIT_MATCHED[-1])
    paged_growth = (results[hi]["paged"]["kv_place_us"]
                    / results[lo]["paged"]["kv_place_us"])
    contig_growth = (results[hi]["contiguous"]["kv_place_us"]
                     / results[lo]["contiguous"]["kv_place_us"])
    speedup_8k = (results[hi]["contiguous"]["kv_place_us"]
                  / results[hi]["paged"]["kv_place_us"])
    # asymptotic shape via least-squares MARGINAL cost (us per matched
    # token) — endpoint ratios are polluted by the ~ms fixed dispatch
    # overhead both layouts pay, slopes are not
    xs = np.asarray(ADMIT_MATCHED, np.float64)
    slope = {
        layout: float(np.polyfit(
            xs, [results[str(m)][layout]["kv_place_us"]
                 for m in ADMIT_MATCHED], 1)[0])
        for layout in ("contiguous", "paged")
    }
    slope_ratio = slope["contiguous"] / max(slope["paged"], 1e-4)
    # O(1) vs O(matched): paged must stay ~flat across the 8x sweep while
    # the contiguous marginal cost is at least 5x steeper; thresholds are
    # deliberately loose so scheduler noise can't flip the verdict
    ok = paged_growth < 2.0 and slope_ratio > 5.0 and speedup_8k > 2.0
    append_record("serve_paged_admission", {
        "block_size": ADMIT_BLOCK,
        "tail_len": ADMIT_TAIL,
        "matched": results,
        "paged_growth_1k_to_8k": round(paged_growth, 2),
        "contiguous_growth_1k_to_8k": round(contig_growth, 2),
        "us_per_matched_token": {k: round(v, 4) for k, v in slope.items()},
        "marginal_cost_ratio": round(slope_ratio, 1),
        "kv_place_speedup_at_8k": round(speedup_8k, 2),
        "pass": ok,
    })
    print(f"# paged admission: paged growth {paged_growth:.2f}x (flat), "
          f"marginal cost {slope['contiguous']:.3f} vs "
          f"{slope['paged']:.3f} us/tok ({slope_ratio:.0f}x steeper), "
          f"8k placement speedup {speedup_8k:.1f}x "
          f"({'PASS' if ok else 'FAIL'})")
    return ok


# chunked-prefill interference bench: one long prompt amid short traffic
MIX_LONG = 2048
MIX_CHUNK = 128
MIX_MAX_LEN = MIX_LONG + 64      # slots provisioned for the workload
MIX_RESIDENT = 2                 # long-decode requests holding slots
MIX_SHORTS = 4                   # short prompts arriving behind the long one


def _mixed_workload(rng, vocab):
    """(residents, long_req, shorts_after).

    Two residents decode throughout (the stall witnesses — slots stay
    free for admission), then the long prompt arrives with short
    interactive requests right behind it.  Monolithic prefill blocks
    the engine — and therefore both the residents' decode service and
    the shorts' admission — for the long prompt's entire prefill;
    chunked admits the shorts at the next step boundary and bounds each
    decode gap by one chunk.  Short prompts use a fixed length so both
    passes share one jit shape."""
    residents = [Request(rid=i, prompt=rng.integers(
        0, vocab, 24).astype(np.int32), max_new_tokens=48)
        for i in range(MIX_RESIDENT)]
    long_req = Request(rid=100, prompt=rng.integers(
        0, vocab, MIX_LONG).astype(np.int32), max_new_tokens=4)
    after = [Request(rid=200 + i, prompt=rng.integers(
        0, vocab, 24).astype(np.int32), max_new_tokens=8)
        for i in range(MIX_SHORTS)]
    return residents, long_req, after


def _run_mixed(eng, workload) -> dict:
    residents, long_req, after = workload
    for r in residents:
        eng.submit(r)
    for _ in range(6):               # residents placed and mid-decode
        eng.step()
    for r in [long_req] + after:
        eng.submit(r)
    eng.run()
    outs = {r.rid: list(map(int, r.out))
            for r in residents + [long_req] + after}
    return {
        "stall_p95_ms": percentile(eng.stats.decode_stall_s, 95) * 1e3,
        "stall_max_ms": percentile(eng.stats.decode_stall_s, 100) * 1e3,
        "ttft_short_p95_ms": percentile(
            [r.ttft_s for r in after], 95) * 1e3,
        "ttft_long_ms": round(long_req.ttft_s * 1e3, 2),
        "prefill_chunks": eng.stats.prefill_chunks,
        "outs": outs,
    }


def bench_chunked_prefill(cfg, params) -> bool:
    """Mixed workload: decode-stall p95 + short-prompt TTFT p95,
    chunked (128-token) vs monolithic (whole-chunk) paged prefill."""
    results = {}
    for mode, chunk in (("monolithic", "whole"), ("chunked", MIX_CHUNK)):
        rng = np.random.default_rng(3)      # identical workload per mode
        eng = ContinuousBatchingEngine(
            cfg, params, slots=SLOTS, max_len=MIX_MAX_LEN,
            block_size=16, kv_layout="paged", prefill_chunk=chunk)
        # warm pass compiles every chunk/prompt shape out of the timed run
        _run_mixed(eng, _mixed_workload(rng, cfg.vocab_size))
        eng.stats = type(eng.stats)()
        rng = np.random.default_rng(4)
        r = _run_mixed(eng, _mixed_workload(rng, cfg.vocab_size))
        results[mode] = r
        print(f"# mixed {mode:>10}: stall p95 {r['stall_p95_ms']:7.2f}ms "
              f"(max {r['stall_max_ms']:7.2f}ms), short ttft p95 "
              f"{r['ttft_short_p95_ms']:7.2f}ms, long ttft "
              f"{r['ttft_long_ms']:7.2f}ms, {r['prefill_chunks']} chunks")
    parity = results["monolithic"].pop("outs") == results["chunked"].pop("outs")
    stall_ratio = (results["monolithic"]["stall_p95_ms"]
                   / max(results["chunked"]["stall_p95_ms"], 1e-6))
    ttft_improved = (results["chunked"]["ttft_short_p95_ms"]
                     < results["monolithic"]["ttft_short_p95_ms"])
    ok = parity and stall_ratio >= 3.0 and ttft_improved
    append_record("serve_chunked_prefill", {
        "long_prompt": MIX_LONG,
        "chunk": MIX_CHUNK,
        "n_short": MIX_SHORTS,
        "monolithic": results["monolithic"],
        "chunked": results["chunked"],
        "decode_stall_p95_ratio": round(stall_ratio, 2),
        "short_ttft_p95_improved": ttft_improved,
        "greedy_parity": parity,
        "pass": ok,
    })
    print(f"# chunked prefill: decode-stall p95 {stall_ratio:.1f}x lower, "
          f"short ttft p95 {'improved' if ttft_improved else 'WORSE'}, "
          f"parity {'exact' if parity else 'BROKEN'} "
          f"({'PASS' if ok else 'FAIL'}: need >=3x at exact parity)")
    return ok


# fused decode-horizon bench: decode-bound sweep + admission pressure
HZN_CHOICES = ("1", "4", "16", "auto")
HZN_PROMPT = 16
HZN_NEW = 64                     # decode-bound: long generations
HZN_REQS = 8
HZN_REPS = 4                     # timed reps; best-of (noisy host)


def _horizon_workload(rng, vocab) -> List[Request]:
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, HZN_PROMPT).astype(np.int32),
                    max_new_tokens=HZN_NEW) for i in range(HZN_REQS)]


def _horizon_engine(cfg, params, horizon):
    # longer trials + periodic re-exploration vs the defaults: single
    # fused-call walls wobble 2-3x on a shared host, so a conclusion
    # needs more evidence, and a conclusion that went the wrong way on
    # a noise spike must be revisitable before the timed pass.  EVERY
    # arm gets the same VPE — a fixed horizon registers no
    # decode_horizon axis, but it tunes serve_decode_impl exactly like
    # the auto arm, so auto-vs-fixed isolates the horizon axis instead
    # of confounding it with decode-attention tuning
    vpe = VPE(controller_kwargs=dict(min_samples=3, trial_samples=16,
                                     hysteresis=0.02, reexplore_period=48))
    # pin the decode-attention axis in EVERY arm (system-tagged ops are
    # measured but never trialed, the paper's system-call exclusion):
    # the sweep is about the horizon axis, and an arm quietly switching
    # attention impls mid-comparison would confound it
    vpe.registry.register_op("serve_decode_impl", system=True)
    for i, name in enumerate(SERVE_AXES["serve_decode_impl"]):
        vpe.registry.register_variant("serve_decode_impl", name,
                                      fn=(lambda name=name: name),
                                      default=(i == 0))
    eng = ContinuousBatchingEngine(
        cfg, params, slots=SLOTS, max_len=MAX_LEN, kv_layout="paged",
        block_size=16, decode_horizon=(horizon if horizon == "auto"
                                       else int(horizon)),
        horizon_choices=(4, 16), vpe=vpe)
    return eng, vpe


def _run_horizon_pass(eng, reqs) -> dict:
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    return {
        "tok_per_s": useful_tokens(reqs) / wall,
        "ttft_p95_ms": percentile(eng.stats.ttft_s, 95) * 1e3,
        "queue_p95_ms": percentile(eng.stats.queue_wait_s, 95) * 1e3,
        "outs": {r.rid: list(map(int, r.out)) for r in reqs},
    }


def _bench_horizon_workload(cfg, params, make_reqs, warm_passes: int) -> dict:
    """One workload over the horizon sweep; best-of-HZN_REPS timed,
    reps INTERLEAVED across arms so a slow host epoch degrades every
    arm alike instead of whichever arm it happened to land on (arms
    measured minutes apart drift 10-20% on the shared container)."""
    from repro.core import bucket_label
    engines = {}
    for label in HZN_CHOICES:
        eng, vpe = _horizon_engine(cfg, params, label)
        for _ in range(warm_passes):   # compiles + controller settling
            _run_horizon_pass(eng, make_reqs())
        # tuning (and its trial/re-explore cost) is the warm-up phase,
        # as everywhere in this bench: freeze exploration so the timed
        # reps measure steady-state serving under the settled policy
        vpe.controller.reexplore_period = 0
        engines[label] = (eng, vpe)
    results: dict = {}
    for _ in range(HZN_REPS):
        for label, (eng, _vpe) in engines.items():
            eng.stats = type(eng.stats)()
            r = _run_horizon_pass(eng, make_reqs())
            # capture per-rep so the persisted hist describes the SAME
            # pass as the throughput it sits next to
            r["horizon_hist"] = dict(eng.stats.horizon_hist)
            if label not in results \
                    or r["tok_per_s"] > results[label]["tok_per_s"]:
                results[label] = r
    for label, (eng, vpe) in engines.items():
        results[label]["selected"] = {
            bucket_label(b): d.selected
            for (op, b), d in vpe.controller._decisions.items()
            if op == "decode_horizon"}
    return results


def bench_decode_horizon(cfg, params) -> bool:
    """Horizon sweep: decode-bound speedup + auto tracking the best
    fixed choice on both a decode-bound and a pressured workload."""
    record = {"slots": SLOTS, "choices": list(HZN_CHOICES)}
    ok = True
    for wname, make_reqs, warm in (
            ("decode_bound",
             lambda: _horizon_workload(np.random.default_rng(5),
                                       cfg.vocab_size), 4),
            ("admission_pressure",
             lambda: make_workload(np.random.default_rng(6), 24,
                                   cfg.vocab_size), 4)):
        res = _bench_horizon_workload(cfg, params, make_reqs, warm)
        outs = {k: v.pop("outs") for k, v in res.items()}
        parity = all(o == outs["1"] for o in outs.values())
        fixed = {k: v["tok_per_s"] for k, v in res.items() if k != "auto"}
        best_fixed = max(fixed, key=fixed.get)
        speedup = fixed[best_fixed] / fixed["1"]
        auto_ratio = res["auto"]["tok_per_s"] / fixed[best_fixed]
        w_ok = parity and auto_ratio >= 0.9
        if wname == "decode_bound":
            w_ok = w_ok and speedup >= 1.5
        ok = ok and w_ok
        record[wname] = {
            "results": res,
            "best_fixed": best_fixed,
            "best_fixed_speedup_vs_1": round(speedup, 2),
            "auto_vs_best_fixed": round(auto_ratio, 3),
            "greedy_parity": parity,
        }
        for label in HZN_CHOICES:
            print(f"# horizon {wname:>18} H={label:>4}: "
                  f"{res[label]['tok_per_s']:8.1f} tok/s, ttft p95 "
                  f"{res[label]['ttft_p95_ms']:7.2f}ms, queue p95 "
                  f"{res[label]['queue_p95_ms']:7.2f}ms")
        print(f"# horizon {wname}: best fixed H={best_fixed} "
              f"({speedup:.2f}x vs H=1), auto at {auto_ratio:.2f}x of best, "
              f"parity {'exact' if parity else 'BROKEN'}")
        if "selected" in res["auto"]:
            print(f"# horizon {wname} auto decisions: "
                  f"{res['auto']['selected']}")
    record["pass"] = ok
    append_record("serve_decode_horizon", record)
    print(f"# decode horizon: {'PASS' if ok else 'FAIL'} "
          f"(need >=1.5x decode-bound at the best fixed horizon and "
          f"auto within 10% of best on both workloads, exact parity)")
    return ok


PRIO_REQS = 32
PRIO_BUDGET = 12   # 4 slots x nb_max=6 wants 24+ pages worst case; floor is 8
PRIO_REPS = 2


def _priority_workload(rng, vocab) -> List[Request]:
    """Deep burst: ~1/3 short interactive turns buried among long batch
    generations, all submitted at once — so under FIFO, submit order
    alone decides when an interactive request reaches a slot."""
    reqs = []
    for i in range(PRIO_REQS):
        if i % 3 == 2:
            prompt = rng.integers(0, vocab, int(rng.integers(6, 13)))
            new, prio = 4, "interactive"
        else:
            prompt = rng.integers(0, vocab, int(rng.integers(24, 49)))
            new, prio = 24, "batch"
        reqs.append(Request(rid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=new, priority=prio))
    return reqs


def _priority_engine(cfg, params) -> ContinuousBatchingEngine:
    return ContinuousBatchingEngine(
        cfg, params, slots=SLOTS, max_len=MAX_LEN, kv_layout="paged",
        block_size=16, prefix_blocks=4, page_budget=PRIO_BUDGET,
        swap=True, slo_weight=0.25,
        max_skip_by_class={"interactive": 8, "batch": 4})


def _run_priority_pass(eng, reqs) -> dict:
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    return {
        "tok_per_s": useful_tokens(reqs) / wall,
        "ttft_by_rid": {r.rid: r.ttft_s for r in reqs},
        "outs": {r.rid: list(map(int, r.out)) for r in reqs},
        "preemptions": eng.stats.preemptions,
        "swap_outs": eng.stats.swap_outs,
        "swap_ins": eng.stats.swap_ins,
        "placement_rollbacks": eng.stats.placement_rollbacks,
    }


def bench_priority_mix(cfg, params) -> bool:
    """Part 6: identical over-pressure burst through identically
    configured engines (page_budget far below worst case, swap on) —
    once with every request submitted class-blind as batch traffic
    (FIFO arm), once with the real priority classes.  Class-aware
    scheduling must buy interactive TTFT and SLO attainment without
    giving back aggregate throughput, at exact greedy parity."""
    rng = np.random.default_rng(8)
    base = _priority_workload(rng, cfg.vocab_size)
    inter = sorted(r.rid for r in base if r.priority == "interactive")

    def arm_reqs(fifo: bool) -> List[Request]:
        reqs = copy.deepcopy(base)
        if fifo:
            for r in reqs:
                r.priority = "batch"   # class-blind: submit order rules
        return reqs

    engines = {}
    for arm in ("fifo", "priority"):
        eng = _priority_engine(cfg, params)
        for _ in range(2):   # compiles: prefill buckets + the swap jits
            _run_priority_pass(eng, arm_reqs(arm == "fifo"))
        engines[arm] = eng
    results: dict = {}
    # reps interleaved across arms, best tok/s kept — same noise
    # discipline as the horizon sweep (a slow host epoch degrades both
    # arms alike instead of whichever one it lands on)
    for _ in range(PRIO_REPS):
        for arm, eng in engines.items():
            eng.stats = type(eng.stats)()
            r = _run_priority_pass(eng, arm_reqs(arm == "fifo"))
            if arm not in results \
                    or r["tok_per_s"] > results[arm]["tok_per_s"]:
                results[arm] = r

    outs = {arm: r.pop("outs") for arm, r in results.items()}
    parity = outs["fifo"] == outs["priority"]
    # the SLO deadline is the FIFO arm's overall median TTFT — a
    # host-speed-independent "typical latency on this box" bar.  FIFO
    # spreads interactive requests through the queue, so roughly half
    # miss it; a class-aware scheduler should land nearly all of them
    # under it.
    slo_s = float(np.median(list(results["fifo"]["ttft_by_rid"].values())))
    for arm, r in results.items():
        tt = r.pop("ttft_by_rid")
        ti = [tt[rid] for rid in inter]
        tb = [tt[rid] for rid in tt if rid not in set(inter)]
        r["tok_per_s"] = round(r["tok_per_s"], 1)
        r["interactive_ttft_p50_ms"] = round(percentile(ti, 50) * 1e3, 2)
        r["interactive_ttft_p95_ms"] = round(percentile(ti, 95) * 1e3, 2)
        r["batch_ttft_p50_ms"] = round(percentile(tb, 50) * 1e3, 2)
        r["batch_ttft_p95_ms"] = round(percentile(tb, 95) * 1e3, 2)
        r["slo_attainment"] = round(sum(t <= slo_s for t in ti) / len(ti), 3)

    f, p = results["fifo"], results["priority"]
    ok = (parity
          and p["interactive_ttft_p95_ms"] < f["interactive_ttft_p95_ms"]
          and p["slo_attainment"] > f["slo_attainment"]
          and p["tok_per_s"] >= 0.85 * f["tok_per_s"])
    record = {
        "slots": SLOTS,
        "page_budget": PRIO_BUDGET, "n_requests": PRIO_REQS,
        "n_interactive": len(inter), "swap": True,
        "slo_ms": round(slo_s * 1e3, 2),
        "fifo": f, "priority": p,
        "greedy_parity": parity, "pass": ok,
    }
    for arm in ("fifo", "priority"):
        r = results[arm]
        print(f"# priority {arm:>8}: {r['tok_per_s']:8.1f} tok/s, "
              f"interactive ttft p50/p95 "
              f"{r['interactive_ttft_p50_ms']:7.2f}/"
              f"{r['interactive_ttft_p95_ms']:7.2f}ms, "
              f"attainment {r['slo_attainment']:.2f}, "
              f"preempt {r['preemptions']}, swap {r['swap_outs']}/"
              f"{r['swap_ins']}, rollbacks {r['placement_rollbacks']}")
    append_record("serve_priority_mix", record)
    print(f"# priority mix: {'PASS' if ok else 'FAIL'} "
          f"(need interactive ttft p95 and SLO attainment strictly "
          f"better than FIFO at >=0.85x its tok/s, exact parity)")
    return ok


# shard sweep: same decode workload served at mp in {1, 2, 4} over
# jax.devices(), all in this process — a chip belongs to one process, so
# children started after this process has touched JAX could never get
# one.  On the CPU the caller provides the devices before JAX starts:
# XLA_FLAGS=--xla_force_host_platform_device_count=8.  Forced host
# "devices" share one CPU, so mp>1 cannot be faster there; the sweep pins
# token-exact parity and leak-free drain per width (the dispatch keys
# carry the shard bucket, so tok/s per width is still a real measured
# point).
SHARD_MPS = (1, 2, 4)
SHARD_REQS = 12


def _shard_workload(vocab: int) -> List[Request]:
    """Decode-bound and deterministic: identical for every mesh width so
    outputs are comparable token-for-token."""
    rng = np.random.default_rng(11)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, 16).astype(np.int32),
                    max_new_tokens=24)
            for i in range(SHARD_REQS)]


def _shard_point(cfg, params, mp: int) -> dict:
    """Serve the fixed workload at mesh (1, mp): one warm pass, one timed."""
    eng = make_serve_engine(cfg, params, mesh_shape=(1, mp), slots=SLOTS,
                            max_len=MAX_LEN, kv_layout="paged", block_size=16)
    run_engine(eng, _shard_workload(cfg.vocab_size))   # warm: compiles
    eng.stats = type(eng.stats)()
    r = run_engine(eng, _shard_workload(cfg.vocab_size))
    eng.check_kv()   # raises on any leaked page / dangling reference
    return {
        "mp": mp,
        "devices": jax.device_count(),
        "kv_heads_sharded": cfg.num_kv_heads % mp == 0,
        "tok_per_s": round(r["tok_per_s"], 1),
        "ttft_p95_ms": round(r["ttft_p95_ms"], 2),
        "kv_clean_at_drain": True,
        "outs": {str(k): v for k, v in r["outs"].items()},
    }


def bench_shard_sweep(cfg, params) -> bool:
    """mp sweep in this process; parity vs mp=1 and leak-free drain."""
    need = max(SHARD_MPS)
    if jax.device_count() < need:
        print(f"# shard sweep: FAIL (needs {need} devices, "
              f"{jax.device_count()} visible; on the CPU run with "
              f"XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return False
    results = {str(mp): _shard_point(cfg, params, mp) for mp in SHARD_MPS}
    outs = {k: v.pop("outs") for k, v in results.items()}
    parity = all(o == outs["1"] for o in outs.values())
    clean = all(v["kv_clean_at_drain"] for v in results.values())
    ok = parity and clean
    append_record("serve_shard_sweep", {
        "slots": SLOTS, "n_requests": SHARD_REQS, "kv_layout": "paged",
        "mesh": {k: v for k, v in results.items()},
        "greedy_parity": parity, "kv_clean_at_drain": clean, "pass": ok,
    })
    for mp in SHARD_MPS:
        r = results[str(mp)]
        print(f"# shard mp={mp}: {r['tok_per_s']:8.1f} tok/s, ttft p95 "
              f"{r['ttft_p95_ms']:7.2f}ms, kv heads "
              f"{'sharded' if r['kv_heads_sharded'] else 'replicated'}")
    print(f"# shard sweep: parity {'exact' if parity else 'BROKEN'}, "
          f"drain {'clean' if clean else 'LEAKED'} "
          f"({'PASS' if ok else 'FAIL'}: need token-exact parity and "
          f"zero leaked pages at every mesh width)")
    return ok


# -- part 8 (PR 8): kernel backend sweep (gather vs pallas vs auto) ----------

KRN_ARMS = ("gather", "pallas", "auto")
KRN_REPS = 2


def _kernel_workload(kind: str, vocab: int) -> List[Request]:
    """Small on purpose: the pallas arm runs interpreted on CPU (a
    correctness-plus-dispatch gate here, the real measurement is a TPU
    re-run), and interpret-mode wall scales with tokens scored."""
    rng = np.random.default_rng(13)
    if kind == "decode_bound":     # short prompts, long generations
        return [Request(rid=i,
                        prompt=rng.integers(0, vocab, 10).astype(np.int32),
                        max_new_tokens=20) for i in range(8)]
    return [Request(rid=i,        # prefill_heavy: long prompts, short tails
                    prompt=rng.integers(0, vocab, 48).astype(np.int32),
                    max_new_tokens=4) for i in range(6)]


def _kernel_engine(cfg, params, arm):
    """One engine per arm.  Pinned arms fix BOTH kernel axes (the engine
    registers serve_decode_impl as a system op — recorded, never
    trialed); the auto arm leaves both measured.  Chunk size and horizon
    are fixed in every arm so the sweep isolates the kernel axes."""
    vpe = VPE(controller_kwargs=dict(min_samples=3, trial_samples=8,
                                     hysteresis=0.02, reexplore_period=48))
    decode_impl = ("auto" if arm == "auto"
                   else ("grouped" if arm == "gather" else "pallas"))
    eng = ContinuousBatchingEngine(
        cfg, params, slots=SLOTS, max_len=MAX_LEN, kv_layout="paged",
        block_size=16, prefill_chunk=16, decode_horizon=4,
        decode_impl=decode_impl, prefill_kernel=arm, vpe=vpe)
    return eng, vpe


def _run_kernel_pass(eng, reqs) -> dict:
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    eng.check_kv()   # raises on any leaked page at drain
    return {
        "tok_per_s": useful_tokens(reqs) / wall,
        "ttft_p95_ms": percentile(eng.stats.ttft_s, 95) * 1e3,
        "outs": {r.rid: list(map(int, r.out)) for r in reqs},
    }


def _bench_kernel_workload(cfg, params, kind: str) -> dict:
    """One workload over the three arms; reps interleaved across arms
    (same shared-host discipline as the horizon sweep), auto's trial
    and settling cost confined to the warm passes."""
    from repro.core import bucket_label
    engines = {}
    for arm in KRN_ARMS:
        eng, vpe = _kernel_engine(cfg, params, arm)
        warm = 4 if arm == "auto" else 2   # auto also settles its trials
        for _ in range(warm):
            _run_kernel_pass(eng, _kernel_workload(kind, cfg.vocab_size))
        vpe.controller.reexplore_period = 0
        engines[arm] = (eng, vpe)
    results: dict = {}
    for _ in range(KRN_REPS):
        for arm, (eng, _vpe) in engines.items():
            eng.stats = type(eng.stats)()
            r = _run_kernel_pass(eng, _kernel_workload(kind, cfg.vocab_size))
            if arm not in results \
                    or r["tok_per_s"] > results[arm]["tok_per_s"]:
                results[arm] = r
    _eng, vpe = engines["auto"]
    results["auto"]["selected"] = {
        op: {bucket_label(b): d.selected
             for (o, b), d in vpe.controller._decisions.items() if o == op}
        for op in ("serve_decode_impl", "prefill_kernel")}
    return results


def bench_kernel_sweep(cfg, params) -> bool:
    """Gather vs pallas vs auto on a decode-bound and a prefill-heavy
    workload: token parity across backends is the gate (on CPU the
    pallas arm runs interpreted, so relative tok/s is reported, not
    asserted — auto converging AWAY from interpreted pallas is the
    dispatch loop working); per-bucket auto selections are recorded
    for both kernel axes."""
    record = {"slots": SLOTS, "arms": list(KRN_ARMS),
              "prefill_chunk": 16, "decode_horizon": 4}
    ok = True
    for kind in ("decode_bound", "prefill_heavy"):
        res = _bench_kernel_workload(cfg, params, kind)
        outs = {k: v.pop("outs") for k, v in res.items()}
        parity = all(o == outs["gather"] for o in outs.values())
        ok = ok and parity
        record[kind] = {
            "results": res,
            "pallas_vs_gather": round(
                res["pallas"]["tok_per_s"] / res["gather"]["tok_per_s"], 3),
            "greedy_parity": parity,
        }
        for arm in KRN_ARMS:
            print(f"# kernel {kind:>13} {arm:>6}: "
                  f"{res[arm]['tok_per_s']:8.1f} tok/s, ttft p95 "
                  f"{res[arm]['ttft_p95_ms']:7.2f}ms")
        print(f"# kernel {kind}: parity "
              f"{'exact' if parity else 'BROKEN'}; auto selections: "
              f"{res['auto']['selected']}")
    record["pass"] = ok
    append_record("serve_kernel_sweep", record)
    print(f"# kernel sweep: {'PASS' if ok else 'FAIL'} "
          f"(need token-exact greedy parity across backends on both "
          f"workloads, zero leaked pages at every drain)")
    return ok


# -- part 9 (PR 9): speculative decoding sweep (off vs fixed vs auto) --------
SPEC_ARMS = ("off", "4", "16", "auto")
# auto's verify spans (+ the "off" incumbent).  16 FIRST: the controller
# blind-trials untried variants in registration order, and over a
# 16-step fused horizon the wide span is the one with headroom (a
# 4-token verify replaces a 16-token fused call — even at full accept it
# commits a quarter of the tokens for most of the dispatch cost, which
# is exactly why the pinned-4 arm is in the sweep as the cautionary
# middle ground), so the promising candidate must not queue behind it
SPEC_CHOICES = (16, 4)
SPEC_HORIZON = 16                # part 5's best fixed horizon, every arm
SPEC_REPS = 4
SPEC_WARM = 8                    # compiles + proposer warm-up + axis trials


def _spec_repetitive(vocab) -> List[Request]:
    """Two shared 16-token templates, long generations: after one warm
    pass the proposer's table holds each template's whole greedy stream,
    so drafts replay it and verify calls commit multi-token runs.  The
    SAME workload every pass — repetition is the point.  Two waves over
    SLOTS (not a deep queue): queue depth is a component of the spec
    axis's bucket key, and a deep queue would scatter auto's evidence
    across queue-depth levels the steady state never revisits."""
    rng = np.random.default_rng(7)
    tpls = [rng.integers(0, vocab, 16).astype(np.int32) for _ in range(2)]
    return [Request(rid=i, prompt=tpls[i % 2].copy(), max_new_tokens=80)
            for i in range(2 * SLOTS)]


def _spec_adversarial(rng, vocab) -> List[Request]:
    """Zero repetition: fresh unique prompts drawn from an ADVANCING rng
    (a repeated pass would warm the table and stop being adversarial),
    so n-gram drafts essentially never land."""
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab,
                                        int(rng.integers(8, 21))
                                        ).astype(np.int32),
                    max_new_tokens=48) for i in range(16)]


def _spec_engine(cfg, params, arm):
    # same isolation discipline as the horizon sweep: every arm gets the
    # same VPE with the decode-attention axis pinned system-side, and
    # the fused horizon pinned to part 5's decode-bound winner, so
    # off-vs-fixed-vs-auto isolates the spec_draft axis alone.
    # min_samples is deliberately high for this axis: a spec trial's
    # outcome depends on proposer-table warmth (an early trial measures
    # the cold table, not the span), so the incumbent must accumulate
    # evidence — i.e. the table must see the workload — before the
    # first blind offload fires
    vpe = VPE(controller_kwargs=dict(min_samples=6, trial_samples=4,
                                     hysteresis=0.02, reexplore_period=24))
    vpe.registry.register_op("serve_decode_impl", system=True)
    for i, name in enumerate(SERVE_AXES["serve_decode_impl"]):
        vpe.registry.register_variant("serve_decode_impl", name,
                                      fn=(lambda name=name: name),
                                      default=(i == 0))
    spec = arm if arm in ("off", "auto") else int(arm)
    # occupancy_levels=2: the sweep's workloads hold occupancy near full
    # during the phase that matters (decode-bound steady state), so the
    # default 4-level occupancy key only fragments the spec axis's
    # trials across buckets the workload barely revisits
    eng = ContinuousBatchingEngine(
        cfg, params, slots=SLOTS, max_len=MAX_LEN, kv_layout="paged",
        block_size=16, decode_horizon=SPEC_HORIZON, occupancy_levels=2,
        spec_draft=spec, spec_choices=SPEC_CHOICES, vpe=vpe)
    return eng, vpe


def _run_spec_pass(eng, reqs) -> dict:
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    st = eng.stats
    return {
        "tok_per_s": useful_tokens(reqs) / wall,
        "spec_calls": st.spec_calls,
        "accept_rate": round(st.accepted_tokens / st.draft_tokens, 3)
                       if st.draft_tokens else 0.0,
        "accept_hist": {str(k): v for k, v in sorted(st.accept_hist.items())},
        "outs": {r.rid: list(map(int, r.out)) for r in reqs},
    }


def _bench_spec_workload(cfg, params, passes) -> dict:
    """One workload (a list of per-pass request lists, shared by every
    arm so parity is comparable rep-by-rep) over the three arms; reps
    interleaved across arms, tuning confined to the warm passes."""
    from repro.core import bucket_label
    engines = {}
    for arm in SPEC_ARMS:
        eng, vpe = _spec_engine(cfg, params, arm)
        for p in range(SPEC_WARM):
            _run_spec_pass(eng, copy.deepcopy(passes[p]))
        vpe.controller.reexplore_period = 0
        engines[arm] = (eng, vpe)
    results: dict = {}
    parity = True
    for rep in range(SPEC_REPS):
        outs = {}
        for arm, (eng, _vpe) in engines.items():
            eng.stats = type(eng.stats)()
            r = _run_spec_pass(eng, copy.deepcopy(passes[SPEC_WARM + rep]))
            outs[arm] = r.pop("outs")
            if arm not in results \
                    or r["tok_per_s"] > results[arm]["tok_per_s"]:
                results[arm] = r
        # arms at the same rep served the SAME requests — outputs must
        # match token for token whatever the drafts did
        parity = parity and all(o == outs["off"] for o in outs.values())
    for arm, (eng, _vpe) in engines.items():
        # leak-free drain on every arm: rejected-tail rollback really
        # returned the reserved pages the accept mask never reached
        # (check_kv audits pool refcounts == tree + live block tables;
        # drained slots must hold no pages at all)
        eng.check_kv()
        assert all(not s.pages for s in eng.slots)
    _eng, vpe = engines["auto"]
    results["auto"]["selected"] = {
        bucket_label(b): d.selected
        for (op, b), d in vpe.controller._decisions.items()
        if op == "spec_draft"}
    results["parity"] = parity
    return results


def bench_spec_sweep(cfg, params) -> bool:
    """Speculative decoding sweep: repetitive-workload speedup over the
    best non-speculative fixed horizon, adversarial-workload back-off,
    parity + clean drains everywhere."""
    record = {"slots": SLOTS, "arms": list(SPEC_ARMS),
              "spec_choices": list(SPEC_CHOICES),
              "decode_horizon": SPEC_HORIZON}
    adv_rng = np.random.default_rng(11)
    ok = True
    for wname, passes in (
            ("repetitive",
             [_spec_repetitive(cfg.vocab_size)
              for _ in range(SPEC_WARM + SPEC_REPS)]),
            ("adversarial",
             [_spec_adversarial(adv_rng, cfg.vocab_size)
              for _ in range(SPEC_WARM + SPEC_REPS)])):
        res = _bench_spec_workload(cfg, params, passes)
        parity = res.pop("parity")
        rates = {k: v["tok_per_s"] for k, v in res.items()}
        best_arm = max(rates, key=rates.get)
        auto_ratio = rates["auto"] / rates[best_arm]
        sel = res["auto"]["selected"]
        w_ok = parity and auto_ratio >= 0.9
        record_w = {
            "results": res,
            "best_arm": best_arm,
            "auto_vs_best": round(auto_ratio, 3),
            "greedy_parity": parity,
        }
        if wname == "repetitive":
            # the tentpole claim: speculation must clear 1.3x over the
            # plain engine at ITS best fixed horizon (the off arm)
            speedup = max(rates[a] for a in SPEC_ARMS
                          if a != "off") / rates["off"]
            record_w["best_spec_speedup_vs_off"] = round(speedup, 2)
            w_ok = w_ok and speedup >= 1.3
        else:
            # back-off evidence: at least one concluded bucket chose to
            # stop speculating once the accept rate collapsed
            backed_off = any(v == "off" for v in sel.values())
            record_w["backed_off"] = backed_off
            w_ok = w_ok and backed_off
        ok = ok and w_ok
        record[wname] = record_w
        for arm in SPEC_ARMS:
            print(f"# spec {wname:>12} {arm:>5}: "
                  f"{res[arm]['tok_per_s']:8.1f} tok/s, accept "
                  f"{res[arm]['accept_rate']:5.1%}, "
                  f"{res[arm]['spec_calls']} verify calls")
        print(f"# spec {wname}: best arm {best_arm}, auto at "
              f"{auto_ratio:.2f}x of best, parity "
              f"{'exact' if parity else 'BROKEN'}; auto selections: {sel}")
    record["pass"] = ok
    append_record("serve_spec_sweep", record)
    print(f"# spec sweep: {'PASS' if ok else 'FAIL'} "
          f"(need >=1.3x over off on repetitive, auto within 10% of best "
          f"on both workloads, back-off recorded on adversarial, exact "
          f"parity, leak-free drains)")
    return ok


FAULT_REQS = 24
FAULT_REPS = 2
FAULT_WARM = 2


def _fault_workload(rng, vocab) -> List[Request]:
    """Part 6's shape at part 10's scale: short interactive turns mixed
    with longer batch generations, all submitted at once — the storm
    must not be able to hide behind a uniform workload."""
    reqs = []
    for i in range(FAULT_REQS):
        if i % 3 == 2:
            prompt = rng.integers(0, vocab, int(rng.integers(6, 13)))
            new, prio = 4, "interactive"
        else:
            prompt = rng.integers(0, vocab, int(rng.integers(16, 33)))
            new, prio = 16, "batch"
        reqs.append(Request(rid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=new, priority=prio))
    return reqs


def _storm_plan() -> FaultPlan:
    """One deterministic storm that walks the WHOLE ladder, in order:
    speculation is poisoned then device-faulted (spec -> off), which
    exposes the fused-horizon path; that is NaN'd then stalled
    (horizon -> 1), which exposes the single-token incumbent; that is
    device-faulted and poisoned; a prefill chunk and a page allocation
    fault ride along.  Coordinates are per-site call indices, chosen
    early enough that every spec fires long before the queue drains
    (``exhausted`` is part of the pass criterion).  A fresh plan per
    pass — plans are consumed as they fire."""
    return FaultPlan([
        FaultSpec("spec", "nan", at=1, slot=0,
                  note="poisoned verify logits, one slot"),
        FaultSpec("spec", "device", at=3, note="demotes spec -> off"),
        FaultSpec("fused", "nan", at=1, note="poisoned horizon, all slots"),
        FaultSpec("fused", "stall", at=3, note="demotes horizon -> 1"),
        FaultSpec("decode", "device", at=1),
        FaultSpec("decode", "nan", at=3, slot=1),
        FaultSpec("prefill", "device", at=2),
        FaultSpec("page_alloc", "device", at=10),
    ])


def _fault_engine(cfg, params, plan) -> ContinuousBatchingEngine:
    return ContinuousBatchingEngine(
        cfg, params, slots=SLOTS, max_len=MAX_LEN, kv_layout="paged",
        block_size=16, prefill_chunk=16, decode_horizon=4, spec_draft=4,
        watchdog=True, probation_steps=6, fault_plan=plan)


def _run_fault_pass(eng, reqs) -> dict:
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    st = eng.stats
    inter = sorted(r.ttft_s * 1e3 for r in reqs
                   if r.priority == "interactive" and r.status == "done")
    return {
        "tok_per_s": useful_tokens(reqs) / wall,
        "ttft_p95_ms": round(percentile(inter, 95), 2) if inter else None,
        "device_faults": st.device_faults,
        "numeric_faults": st.numeric_faults,
        "watchdog_trips": st.watchdog_trips,
        "demotions": dict(st.demotions),
        "repromotions": st.repromotions,
        "failed_by_reason": dict(st.failed_by_reason),
        "outs": {r.rid: list(map(int, r.out)) for r in reqs
                 if r.status == "done"},
        "failed": {r.rid: r.error for r in reqs if r.status == "failed"},
    }


def bench_fault_sweep(cfg, params) -> bool:
    """Part 10: clean arm vs fault-storm arm on the same workload.  The
    storm arm must survive every injected fault without the engine
    raising, keep every surviving request token-exact against the
    clean arm, fire the entire plan, demote AND re-promote along the
    ladder, and drain leak-free; tok/s retention is the robustness
    headline (a recovery ladder that recovers by crawling is not a
    recovery ladder)."""
    rng = np.random.default_rng(23)
    base = _fault_workload(rng, cfg.vocab_size)

    engines = {"clean": _fault_engine(cfg, params, None),
               "storm": _fault_engine(cfg, params, _storm_plan())}
    for arm, eng in engines.items():
        for _ in range(FAULT_WARM):
            # the storm arm's warm passes consume a fresh plan each, so
            # the timed pass pays no demoted-path compiles (the
            # single-token incumbent only traces once horizon -> 1)
            if arm == "storm":
                eng.faults = _storm_plan()
            _run_fault_pass(eng, copy.deepcopy(base))

    results: dict = {}
    parity, exhausted, ladder = True, True, True
    for _ in range(FAULT_REPS):
        outs = {}
        for arm, eng in engines.items():
            eng.stats = type(eng.stats)()
            plan = _storm_plan() if arm == "storm" else None
            eng.faults = plan
            r = _run_fault_pass(eng, copy.deepcopy(base))
            outs[arm] = r.pop("outs")
            if plan is not None:
                exhausted = exhausted and plan.exhausted
                ladder = ladder and bool(r["demotions"]) \
                    and r["repromotions"] >= 1
            if arm not in results \
                    or r["tok_per_s"] > results[arm]["tok_per_s"]:
                results[arm] = r
            eng.check_kv()
            assert all(not s.pages for s in eng.slots)
        # every storm survivor must match the clean arm token for token
        # — demotions swap variants and quarantines replay slots, none
        # of which may change what gets emitted
        parity = parity and all(outs["storm"][rid] == outs["clean"][rid]
                                for rid in outs["storm"])
    clean_ok = (results["clean"]["device_faults"] == 0
                and results["clean"]["numeric_faults"] == 0
                and not results["clean"]["failed_by_reason"])
    retention = results["storm"]["tok_per_s"] / results["clean"]["tok_per_s"]
    ok = (parity and exhausted and ladder and clean_ok
          and retention >= 0.4)
    record = {
        "slots": SLOTS, "requests": FAULT_REQS,
        "plan_faults": len(_storm_plan()),
        "clean": {k: v for k, v in results["clean"].items()
                  if k in ("tok_per_s", "ttft_p95_ms")},
        "storm": dict(results["storm"]),
        "retention": round(retention, 3),
        "greedy_parity": parity,
        "plan_exhausted": exhausted,
        "pass": ok,
    }
    record["clean"]["tok_per_s"] = round(record["clean"]["tok_per_s"], 1)
    record["storm"]["tok_per_s"] = round(record["storm"]["tok_per_s"], 1)
    append_record("serve_fault_sweep", record)
    for arm in ("clean", "storm"):
        r = results[arm]
        print(f"# fault {arm:>5}: {r['tok_per_s']:8.1f} tok/s, "
              f"interactive ttft p95 {r['ttft_p95_ms']}ms, "
              f"{r['device_faults']} device / {r['numeric_faults']} numeric "
              f"faults, {r['watchdog_trips']} trips, "
              f"demotions {r['demotions']}, "
              f"{r['repromotions']} repromotions, "
              f"failed {r['failed_by_reason']}")
    print(f"# fault sweep: {'PASS' if ok else 'FAIL'} "
          f"(retention {retention:.2f}x, parity "
          f"{'exact' if parity else 'BROKEN'}, plan "
          f"{'exhausted' if exhausted else 'NOT exhausted'}; need every "
          f"fault fired, demote+repromote observed, exact parity on "
          f"survivors, leak-free drains, retention >= 0.4)")
    return ok


def main(n_requests: int = 24) -> None:
    enable_compile_cache()
    cfg = get_config("qwen3-8b").reduced()
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = make_workload(rng, n_requests, cfg.vocab_size)

    # long-lived servers, as in production: the warm-up pass compiles the
    # jitted steps and lets the VPE controller settle the decode axis
    # (tuning cost is the paper's warm-up phase); the timed pass then
    # measures steady-state serving
    vpe = VPE(controller_kwargs=dict(min_samples=3, trial_samples=3))
    sched = WaveScheduler(ServeLoop(cfg, params, max_len=MAX_LEN, batch=SLOTS))
    eng = ContinuousBatchingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                                   vpe=vpe)
    # warm with the identical workload so neither side pays compiles in
    # the timed pass (wave prefill re-traces per wave span; the engine
    # re-traces per prompt bucket and per trialed decode variant)
    run_wave(sched, copy.deepcopy(reqs))
    run_continuous(eng, copy.deepcopy(reqs))
    eng.stats = type(eng.stats)()  # reset after warm-up

    wave = run_wave(sched, copy.deepcopy(reqs))
    cont = run_continuous(eng, copy.deepcopy(reqs))
    print(f"serve_wave,{wave:.1f}")
    print(f"serve_continuous,{cont:.1f}")
    ok = cont > wave
    print(f"# continuous/wave speedup: {cont / wave:.2f}x "
          f"({'PASS' if ok else 'FAIL'}: continuous must win on "
          f"mixed-length workloads)")
    ok_prefix = bench_prefix_cache(cfg, params, n_requests)
    ok_paged = bench_paged_admission(cfg, params)
    ok_chunked = bench_chunked_prefill(cfg, params)
    ok_horizon = bench_decode_horizon(cfg, params)
    ok_priority = bench_priority_mix(cfg, params)
    ok_shard = bench_shard_sweep(cfg, params)
    ok_kernel = bench_kernel_sweep(cfg, params)
    ok_spec = bench_spec_sweep(cfg, params)
    ok_fault = bench_fault_sweep(cfg, params)
    if not (ok and ok_prefix and ok_paged and ok_chunked and ok_horizon
            and ok_priority and ok_shard and ok_kernel and ok_spec
            and ok_fault):
        sys.exit(1)


if __name__ == "__main__":
    main(n_requests=12 if "--fast" in sys.argv else 24)
