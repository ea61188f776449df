"""Serving runtime: prefill + batched decode, wave and continuous batching.

``ServeLoop.generate`` is the simple batch API (one prefill, N decode
steps, jitted, all rows in lockstep).

:class:`WaveScheduler` is the baseline batcher: requests are grouped
into fixed-size waves and the *whole wave* must finish before the next
queued request starts — queued requests wait behind the slowest member
of the running wave, and every slot decodes until the wave's longest
``max_new_tokens``.  (This class used to be called ``BatchScheduler``
and its docstring overstated it as continuous batching; the alias is
kept for compatibility.)

:class:`ContinuousBatchingEngine` is token-level continuous batching: a
fixed pool of decode slots, each sequence tracks its own length and EOS
state, a finished sequence frees its slot *mid-decode*, and queued
requests are admitted by prefilling into the freed slot while the other
slots keep decoding.  The decode step is the serving hot path and is
wired through the VPE static-dispatch path: decode-attention
implementations are an ``IMPL_AXES``-style axis keyed by slot-occupancy
buckets, the controller's blind-offload/revert loop trials them online,
and a selection change (``controller.version``) re-jits the step — the
paper's function-pointer swap at re-trace boundaries.

Since PR 3 the KV *memory layout* itself is a dispatch axis
(``kv_layout``): each slot holds its sequence either in the contiguous
per-slot cache region (PR 1/2) or as a **block table** of page ids into
the unified device page pool — the paged layout, where prefix-cache
admission aliases cached pages zero-copy (copy-on-write on a partially
matched tail block) and decode attention reads pages through the table.
``kv_layout="auto"`` lets the VPE controller pick per admission, keyed
by matched-prefix-length × occupancy buckets and fed from measured
admission + decode wall time — the paper's measured keep-or-revert
applied to a memory-layout decision.

Since PR 4 prefill is no longer an atomic call on the paged layout:
admission only *places* a request (alias + copy-on-write + page
allocation + block-table install — O(1) in both matched and prompt
length) and the prompt is then prefilled in fixed-size **chunks**
(:func:`~repro.models.transformer.prefill_chunk_paged`) that read all
prior positions through the block table in place.  Each engine step
runs at most ``chunks_per_step`` chunks before the decode step, so the
decode tail latency of resident requests — and the TTFT of short
prompts behind a long one — is bounded by the chunk budget instead of
by the longest queued prompt.  The chunk size itself is a measured
dispatch axis (``prefill_chunk``), keyed by prompt-length × occupancy
buckets and fed from the summed per-chunk wall at prefill completion.

Since PR 5 the decode step can fuse up to ``H`` tokens into ONE jitted
on-device loop per engine iteration (``decode_horizon``): greedy argmax
feeds the next step in-graph, an in-graph stop mask freezes slots that
hit EOS or their token budget mid-horizon, and the host fences once per
horizon on a ``(slots, H)`` token block instead of once per token.  The
horizon is itself a measured dispatch axis keyed by queue-depth ×
occupancy buckets and fed from per-token wall time — the paper's
amortize-dispatch-over-larger-work-items lever (its 32x matmul) turned
into a runtime decision: empty queue → fuse long, contended → stay at
1 so admission latency stays bounded.

Since PR 6 page-pool exhaustion is a *scheduling decision* instead of a
crash: under pressure ``_alloc_page`` escalates from tree eviction to
**victim preemption** — the lowest-priority/youngest prefilling slot is
paused (its entire state is block table + ``fill_pos``, so preemption
is "stop scheduling chunks, release or swap the pages, re-place
later"), with decoding slots as a last resort for decode growth.  With
``swap=True`` a victim's filled pages are gathered to host memory and
scattered back into fresh pages at re-admission (``swap_out``/
``swap_in`` on the :class:`~repro.runtime.page_pool.PagePool`, device
half in :mod:`repro.models.kvcache`); otherwise the work is recomputed.
Requests carry a **priority class** (``interactive``/``batch``) honored
by the admission scheduler alongside the prefix-affinity window, with
the ``max_skip`` starvation bound extendable per class, and a two-term
SLO × throughput objective (``slo_weight``) charges fused horizons and
prefill chunks for the class-weighted queue wait they impose — the
``decode_horizon``/``prefill_chunk`` axes gain a queue-composition
bucket dimension, so "fuse long" loses exactly when someone latency-
sensitive is waiting.  Oversized and empty-prompt submissions become
terminally-failed requests (``status="failed"``, ``error`` set) rather
than caller-visible exceptions.

Since PR 10 the engine is fault tolerant (docs/fault_tolerance.md): a
seeded :class:`~repro.runtime.serve_faults.FaultPlan` can inject device
errors, poisoned logits or fence stalls at any fenced span, and the
engine recovers through a *degradation ladder* that quarantines the
variant before the engine — pallas→gather, spec→off, horizon→1, each a
runtime demotion with VPE re-promotion after a clean probation window;
poisoned logits quarantine only the affected slots (preempt + exact
greedy resume via :meth:`Request.effective_prompt`); an unrecoverable
span fails only the requests it touched, each with a reason code from
``FAIL_REASONS`` and a complete latency record.  Per-request deadlines
(``deadline_s``) and a queue-depth admission bound shed load before the
page pool does, and :class:`EngineReplicaGroup` quarantines a replica
whose step faults terminally or whose watchdog-wrapped fence trips
repeatedly, migrating its in-flight requests onto survivors and
re-admitting it after a canary passes.  The engine itself never raises.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.core import (VPE, decode_horizon_bucket, kv_layout_bucket,
                        occupancy_bucket, pad_to_bucket,
                        prefill_chunk_bucket, prefix_len_bucket,
                        shard_bucket, slo_pressure_bucket,
                        spec_accept_bucket)
from repro.distributed import sharding as sharding_lib
from repro.distributed.straggler import StepWatchdog, StragglerTimeout
from repro.kernels import compat as pallas_compat
from repro.models import kvcache
from repro.models import model as model_lib
from repro.runtime.page_pool import PagePool
from repro.runtime.prefix_cache import PrefixCache
from repro.runtime.serve_faults import FaultPlan, FaultSpec, SimulatedFault
from repro.runtime.spec_decode import NGramProposer

# serve-engine implementation axes (IMPL_AXES analogue):
# * serve_decode_impl — decode-attention layout, keyed by occupancy bucket;
# * prefix_reuse — reuse cached prefix KV pages vs recompute the whole
#   prompt, keyed by matched-prefix-length bucket (the paper's measured
#   keep-or-revert applied to memory reuse instead of compute offload);
# * kv_layout — contiguous slot region vs paged block table, keyed by
#   matched-length × occupancy (only registered for kv_layout="auto");
# * prefill_chunk — prefill chunk size in tokens ("whole" = one chunk),
#   keyed by prompt-length × occupancy (only registered for
#   prefill_chunk="auto"; the registered variant names come from the
#   engine's ``chunk_choices`` — the list below is the canonical set);
# * decode_horizon — how many decode steps to fuse into one on-device
#   loop per engine step, keyed by queue-depth × occupancy (only
#   registered for decode_horizon="auto"; variant names come from the
#   engine's ``horizon_choices``).  Fed from per-TOKEN wall time
#   (dt / valid tokens), so a long horizon wins exactly when amortizing
#   the per-call host overhead beats the admission latency it costs.
# * prefill_kernel — chunk-attention backend for paged prefill: "gather"
#   linearizes pages in-jit, "pallas" reads them in place through the
#   block-indirect kernel.  Keyed by the SAME prefill_chunk_bucket as
#   the prefill_chunk axis (prompt-length × occupancy, + shard tail),
#   fed from the same clean chunk-wall attribution, and only registered
#   when the engine passes the pallas capability gate
#   (docs/kernel_variants.md fallback ladder).  serve_decode_impl's
#   "pallas" variant is the decode-side twin, gated identically.
# * spec_draft — speculative verify span: "off" (the plain fused-
#   horizon path) vs S-position one-pass draft verification, keyed by
#   queue-depth × occupancy × measured accept-rate level (only
#   registered for spec_draft="auto"; variant names come from the
#   engine's ``spec_choices``).  Fed from per-COMMITTED-token wall of
#   the full span — a span whose drafts miss commits ~1 token per call
#   and prices itself out, which is exactly the back-off the axis
#   exists to learn.
SERVE_AXES: Dict[str, List[str]] = {
    "serve_decode_impl": list(kvcache.DECODE_ATTN_VARIANTS),
    "prefix_reuse": ["reuse", "recompute"],
    "kv_layout": ["contiguous", "paged"],
    "prefill_chunk": ["whole", "128", "512", "2048"],
    "decode_horizon": ["1", "4", "16"],
    "prefill_kernel": ["gather", "pallas"],
    "spec_draft": ["off", "4", "16"],
}

KV_LAYOUTS = ("contiguous", "paged", "auto")

# request priority classes, best first.  Rank 0 (interactive) is never
# preempted by rank 1 (batch) and jumps it in the admission window; the
# SLO pressure term weighs a waiting interactive request at 1.0 and a
# batch request at 0.1 (waiting is what batch traffic is FOR).
PRIORITY_CLASSES = ("interactive", "batch")
PRIORITY_RANK: Dict[str, int] = {c: i for i, c in enumerate(PRIORITY_CLASSES)}
SLO_CLASS_WEIGHT: Dict[str, float] = {"interactive": 1.0, "batch": 0.1}

# terminal failure reason codes (``Request.error``); the human-readable
# message lives in ``Request.error_detail``.  A machine-checkable code
# is what lets callers route retries (device_fault: retry elsewhere;
# deadline/capacity: shed; intake: fix the request) and what the
# per-reason counters in ``ServeStats.failed_by_reason`` key on.
FAIL_REASONS = ("intake", "deadline", "capacity", "device_fault",
                "numeric_fault", "replica_lost")


def _intake_error(req: "Request", max_len: int) -> Optional[str]:
    """Why a submission can never be served, or None if it can.

    Shared by :meth:`ContinuousBatchingEngine.submit` and
    :meth:`EngineReplicaGroup.submit` so single-engine and dp-replica
    intake reject the exact same population with the exact same
    messages."""
    need = len(req.prompt) + req.max_new_tokens
    if need > max_len:
        return (f"prompt+max_new_tokens={need} exceeds slot "
                f"capacity max_len={max_len}")
    if len(np.asarray(req.prompt)) == 0:
        return "empty prompt"
    if req.priority not in PRIORITY_RANK:
        return (f"unknown priority class {req.priority!r} "
                f"(choose from {PRIORITY_CLASSES})")
    return None


class _PagePressure(Exception):
    """Page demand exceeded everything eviction + preemption could free.

    Internal control flow only: placement catches it to roll back and
    requeue the admission; decode growth catches it to preempt the
    growing slot itself.  It never escapes the engine."""


@dataclasses.dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens_out: int = 0
    prefill_tokens: int = 0          # tokens produced by prefill, not decode
    decode_steps: int = 0
    rejits: int = 0                  # decode-step re-traces (VPE swaps)
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    queue_wait_s: List[float] = dataclasses.field(default_factory=list)
    # shared-prefix cache counters (0/empty when the cache is disabled)
    prefix_lookups: int = 0
    prefix_hits: int = 0             # admissions that matched a cached prefix
    prefix_tokens_saved: int = 0     # prompt tokens served from cached pages
    # KV-placement wall time per admission: the matched-length-dependent
    # part of admission (contiguous: gather + copy cached pages into the
    # slot region; paged: block-table aliasing + copy-on-write).  The
    # O(matched)-vs-O(1) contrast the paged layout exists for lives in
    # this series — the serve bench plots it against matched length.
    kv_place_s: List[float] = dataclasses.field(default_factory=list)
    paged_admits: int = 0            # admissions served in the paged layout
    cow_copies: int = 0              # partially-matched tail blocks COW'd
    sched_skips: int = 0             # queue entries jumped by prefix-aware
                                     # admission scheduling
    prefill_chunks: int = 0          # chunked-prefill dispatches
    tainted_steps: int = 0           # decode steps that paid a jit compile
                                     # (excluded from per-slot attribution)
    # decode service interruption per engine step: the wall spent in the
    # admission + prefill-chunk phase ahead of a decode step, recorded
    # only when decoding slots were actually waiting.  Monolithic
    # prefill puts whole-prompt walls here; chunking bounds the series
    # by the chunk budget — the mixed-workload bench's p95 target.
    decode_stall_s: List[float] = dataclasses.field(default_factory=list)
    # fused decode horizons: multi-step on-device calls, the tokens they
    # emitted, pages reserved for a horizon but returned unused (EOS
    # froze the slot first), and the horizon length of every decode
    # call — {H: calls}, single-token steps counted under H=1 — the
    # auto axis's full decision record (back-off to 1 included)
    horizon_calls: int = 0
    horizon_tokens: int = 0
    reserved_pages_rolled_back: int = 0
    horizon_hist: Dict[int, int] = dataclasses.field(default_factory=dict)
    # speculative decoding: one-pass verify calls, drafts offered vs
    # accepted (offered = draft positions a slot's budget could still
    # commit, so budget-clamped calls don't deflate the rate), and the
    # per-slot-call acceptance histogram {accepted drafts: occurrences}
    # — the measured signal behind the spec axis's accept-rate bucket
    # level.  All four merge through the generic field-walk in
    # _merge_stats (scalars sum, dicts merge by key).
    spec_calls: int = 0
    draft_tokens: int = 0
    accepted_tokens: int = 0
    accept_hist: Dict[int, int] = dataclasses.field(default_factory=dict)
    # effective prefill-chunk budget per step that ran chunks — {budget:
    # steps}; adaptive budgeting raises it when no decoding slot could
    # be stalled, the explicit chunks_per_step override pins it
    chunk_budget_hist: Dict[int, int] = dataclasses.field(default_factory=dict)
    # QoS / pressure recovery (PR 6): terminally-failed submissions,
    # slots preempted for pages (decode_preemptions counts the decoding
    # subset — the last-resort tier), host swaps and the pages they
    # moved, and placements aborted all-or-nothing under pressure
    rejected: int = 0
    preemptions: int = 0
    decode_preemptions: int = 0
    swap_outs: int = 0
    swap_ins: int = 0
    swapped_pages: int = 0
    placement_rollbacks: int = 0
    # fault tolerance (PR 10): terminal failures by reason code
    # (FAIL_REASONS — the sum is the failed population), injected/real
    # device faults survived, poisoned-logit events, watchdog fence
    # trips, runtime variant demotions by ladder rung (and the
    # re-promotions that ended a clean probation window), and the
    # replica group's quarantine/canary lifecycle counters
    failed_by_reason: Dict[str, int] = dataclasses.field(default_factory=dict)
    device_faults: int = 0
    numeric_faults: int = 0
    watchdog_trips: int = 0
    demotions: Dict[str, int] = dataclasses.field(default_factory=dict)
    repromotions: int = 0
    replica_quarantines: int = 0
    replica_readmissions: int = 0
    canary_probes: int = 0

    @property
    def decode_tok_per_s(self) -> float:
        if not self.decode_s:
            return 0.0
        return (self.tokens_out - self.prefill_tokens) / self.decode_s

    @property
    def total_tok_per_s(self) -> float:
        """Aggregate throughput: useful tokens over prefill+decode wall."""
        wall = self.prefill_s + self.decode_s
        return self.tokens_out / wall if wall else 0.0

    @property
    def mean_ttft_s(self) -> float:
        return sum(self.ttft_s) / len(self.ttft_s) if self.ttft_s else 0.0

    @property
    def mean_queue_wait_s(self) -> float:
        """Mean queue wait over ADMITTED requests only — intake-rejected
        submissions never waited on scheduling, so they carry their
        (terminal) wait on the request record instead of skewing this
        series.  The population accounting closes through
        :attr:`failed_requests`."""
        return (sum(self.queue_wait_s) / len(self.queue_wait_s)
                if self.queue_wait_s else 0.0)

    @property
    def failed_requests(self) -> int:
        """Terminally-failed submissions (``status="failed"``), all
        reasons: the sum of :attr:`failed_by_reason`.  ``rejected``
        stays the never-admitted subset (intake, capacity, a deadline
        expiring in queue), so the PR 7 population invariant now reads
        ``submitted == len(queue_wait_s) + rejected`` — mid-flight
        failures (device/numeric faults, replica loss, an expired
        running deadline) were admitted and DID record a queue wait."""
        return sum(self.failed_by_reason.values())

    @property
    def prefix_hit_rate(self) -> float:
        return (self.prefix_hits / self.prefix_lookups
                if self.prefix_lookups else 0.0)

    def summary(self) -> str:
        s = (f"{self.tokens_out} tok, {self.total_tok_per_s:.1f} tok/s agg "
             f"({self.decode_tok_per_s:.1f} decode), "
             f"ttft {self.mean_ttft_s * 1e3:.1f}ms, "
             f"queue {self.mean_queue_wait_s * 1e3:.1f}ms, "
             f"{self.rejits} rejits")
        if self.prefix_lookups:
            s += (f", prefix-cache {self.prefix_hits}/{self.prefix_lookups} "
                  f"hits ({self.prefix_tokens_saved} tok saved)")
        if self.paged_admits:
            s += (f", paged {self.paged_admits} admits "
                  f"({self.cow_copies} cow)")
        if self.prefill_chunks:
            s += f", {self.prefill_chunks} prefill chunks"
        if self.horizon_calls:
            s += (f", {self.horizon_calls} fused horizons "
                  f"({self.horizon_tokens} tok)")
        if self.spec_calls:
            rate = (self.accepted_tokens / self.draft_tokens
                    if self.draft_tokens else 0.0)
            s += (f", {self.spec_calls} spec verifies "
                  f"({self.accepted_tokens}/{self.draft_tokens} drafts, "
                  f"{rate:.0%} accept)")
        if self.preemptions:
            s += (f", {self.preemptions} preemptions "
                  f"({self.decode_preemptions} decode)")
        if self.swap_outs:
            s += (f", {self.swap_outs}/{self.swap_ins} swaps out/in "
                  f"({self.swapped_pages} pages)")
        if self.failed_by_reason:
            by = ", ".join(f"{k}:{v}"
                           for k, v in sorted(self.failed_by_reason.items()))
            s += f", {self.failed_requests} failed ({by})"
        if self.device_faults or self.numeric_faults or self.watchdog_trips:
            s += (f", faults survived {self.device_faults} device / "
                  f"{self.numeric_faults} numeric / "
                  f"{self.watchdog_trips} stalls")
        if self.demotions:
            by = ", ".join(f"{k}:{v}"
                           for k, v in sorted(self.demotions.items()))
            s += (f", demotions {by} "
                  f"({self.repromotions} re-promoted)")
        if self.replica_quarantines:
            s += (f", {self.replica_quarantines} replica quarantines "
                  f"({self.replica_readmissions} re-admitted, "
                  f"{self.canary_probes} canaries)")
        return s


class ServeLoop:
    def __init__(self, cfg: ModelConfig, params: Any, *, max_len: int = 256,
                 batch: int = 4, greedy: bool = True) -> None:
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.batch = batch
        self.greedy = greedy
        self._decode = jax.jit(
            lambda p, c, t: model_lib.decode_step(cfg, p, c, t))
        self._prefill = jax.jit(
            lambda p, b, c: model_lib.prefill(cfg, p, b, c))
        self.stats = ServeStats()

    def generate(self, batch_in: Dict[str, np.ndarray], max_new_tokens: int) -> np.ndarray:
        """batch_in: {"tokens": (B, S)} (+frames for encdec) -> (B, new)."""
        B = batch_in["tokens"].shape[0]
        cache = model_lib.init_cache(self.cfg, B, self.max_len)
        t0 = time.perf_counter()
        cache, logits = jax.block_until_ready(
            self._prefill(self.params, jax.tree.map(jnp.asarray, batch_in), cache))
        self.stats.prefill_s += time.perf_counter() - t0
        out = []
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        t0 = time.perf_counter()
        for _ in range(max_new_tokens):
            out.append(np.asarray(tok))
            cache, logits = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        jax.block_until_ready(tok)
        self.stats.decode_s += time.perf_counter() - t0
        self.stats.tokens_out += B * max_new_tokens
        return np.concatenate(out, axis=1)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,)
    max_new_tokens: int
    eos_id: Optional[int] = None
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # filled by the engine: submit wall-clock (queue-wait/TTFT baseline)
    # and the decode-step indices bounding the request's slot residency
    submit_t: float = 0.0
    admit_step: int = -1
    done_step: int = -1
    # per-request latency record (soak invariants: 0 <= queue <= ttft
    # <= done_t - submit_t) and the prefix-cache pin held while resident
    queue_wait_s: float = 0.0
    ttft_s: float = 0.0
    done_t: float = 0.0
    cache_handle: Optional[Any] = None
    # prefix-aware scheduling: times a later-submitted request was
    # admitted ahead of this one (bounded by the engine's max_skip)
    skips: int = 0
    # QoS (PR 6): priority class (see PRIORITY_CLASSES), lifecycle
    # status ("queued" -> "running" -> ["preempted" -> "running"]* ->
    # "done" | "failed"), the terminal error for failed submissions,
    # how many times this request was preempted for pages, and — while
    # preempted with swap on — the host copy of its filled K/V
    # ((k, v, fill_pos), scattered back into fresh pages at
    # re-admission).  ttft_recorded guards the one-ttft-per-request
    # invariant across preempt/resume cycles.
    priority: str = "batch"
    status: str = "queued"
    # fault tolerance (PR 10): ``error`` is a machine-readable reason
    # code from FAIL_REASONS; ``error_detail`` carries the human
    # message that used to live in ``error``.
    error: Optional[str] = None
    error_detail: Optional[str] = None
    preemptions: int = 0
    swap: Optional[Tuple] = None
    ttft_recorded: bool = False
    # wall-clock budget from submit: past ``submit_t + deadline_s`` the
    # request is shed (terminal ``deadline`` failure) wherever the
    # engine next looks at it — the queue sweep or a decode-span
    # boundary — instead of burning device time on an answer nobody is
    # waiting for.  None = no deadline.
    deadline_s: Optional[float] = None
    # fault budget: device/numeric faults charged against this request
    # (quarantine-migration counts too); at the engine's
    # ``max_request_faults`` the request fails terminally instead of
    # retrying forever — the poison-pill bound.
    faults: int = 0
    # replica-group canary probes are engine-internal requests: excluded
    # from group ``completed`` and never migrated off their replica
    canary: bool = False

    def effective_prompt(self) -> np.ndarray:
        """The token prefix a (re-)admission must have in KV before
        decode continues: the prompt plus any tokens already emitted —
        greedy decode is deterministic, so a preempted-and-requeued
        decoding request resumes exactly by prefilling this and decoding
        on (the last emitted token's logits yield the next token)."""
        p = np.asarray(self.prompt, np.int32)
        if not self.out:
            return p
        return np.concatenate([p, np.asarray(self.out, np.int32)])


class WaveScheduler:
    """Wave batching over a fixed decode batch (the baseline).

    A wave of ``serve.batch`` requests runs to completion — left-padded
    to the longest prompt and decoded for the wave's longest
    ``max_new_tokens`` — before the next wave starts.  No mid-decode
    admission: this is what :class:`ContinuousBatchingEngine` is
    benchmarked against.
    """

    def __init__(self, serve: ServeLoop) -> None:
        self.serve = serve
        self.queue: List[Request] = []
        self.completed: List[Request] = []

    def submit(self, req: Request) -> None:
        req.submit_t = time.perf_counter()
        self.queue.append(req)

    def run(self) -> List[Request]:
        B = self.serve.batch
        while self.queue:
            wave, self.queue = self.queue[:B], self.queue[B:]
            span = max(len(r.prompt) for r in wave)
            toks = np.zeros((len(wave), span), np.int32)
            for i, r in enumerate(wave):
                toks[i, -len(r.prompt):] = r.prompt  # left-pad
            new = self.serve.generate({"tokens": toks},
                                      max(r.max_new_tokens for r in wave))
            for i, r in enumerate(wave):
                r.out = list(new[i, : r.max_new_tokens])
                r.done = True
                self.completed.append(r)
        return self.completed


# kept for compatibility with callers of the old (misleading) name
BatchScheduler = WaveScheduler


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    tok: int = 0                 # last generated token (next decode input)
    # paged-layout state (host mirrors of the device block table)
    layout: str = "contiguous"   # KV layout this residency decodes through
    pos: int = 0                 # host mirror of cache["length"][slot]
    pages: List[int] = dataclasses.field(default_factory=list)
    # chunked-prefill state: a paged admission is *placed* instantly and
    # then prefilled chunk-by-chunk between decode steps
    prefilling: bool = False
    fill_pos: int = 0            # prompt positions already prefilled
    chunk: int = 0               # chunk size this admission runs (0 = whole)
    chunk_walls: List[float] = dataclasses.field(default_factory=list)
    # per-chunk SLO-charged cost: wall x (1 + slo_weight x queue
    # pressure at chunk time) — what the prefill_chunk axis optimizes
    # when the two-term objective is on (equal to chunk_walls when off)
    chunk_costs: List[float] = dataclasses.field(default_factory=list)
    chunk_bucket: Optional[Tuple] = None   # prefill_chunk-axis bucket
    chunk_variant: Optional[str] = None
    # prefill_kernel-axis state: which chunk-attention backend this
    # admission's chunks run (resolved through the fallback ladder), and
    # — in auto mode — the bucket/variant its clean chunk walls feed
    kernel: str = "gather"
    kernel_bucket: Optional[Tuple] = None  # prefill_kernel-axis bucket
    kernel_variant: Optional[str] = None
    place_wall: float = 0.0      # the O(1) placement span of this admission
    reuse_bucket: Optional[Tuple] = None   # prefix_reuse sample (fed at
    reuse_variant: str = "reuse"           # prefill completion)
    # kv_layout-axis sample bookkeeping (auto mode): the admission wall
    # (placement + chunk compute), and whether a jit compile landed
    # inside any measured span (tainted samples must not feed the
    # controller — PR 2's rule)
    admit_wall: float = 0.0
    admit_bucket: Optional[Tuple] = None
    tainted: bool = False
    # per-step decode-wall attribution: each engine step's fenced wall is
    # credited to the slots resident for it, EXCLUDING steps that paid a
    # decode-jit compile — this replaces the PR 3 amortized-share-over-
    # the-whole-residency heuristic, whose samples a rejit anywhere in
    # the window could poison (ROADMAP "auto-layout sample quality")
    steps_resident: int = 0
    clean_step_shares: List[float] = dataclasses.field(default_factory=list)

    @property
    def free(self) -> bool:
        return self.req is None


class ContinuousBatchingEngine:
    """Token-level continuous batching over a fixed pool of decode slots.

    Engine iteration (:meth:`step`):

    1. **admit** — while a slot is free and the queue is non-empty, pick
       a request (prefix-aware: see below).  A *contiguous* admission
       prefills the whole prompt atomically and installs its K/V into
       the slot; a *paged* admission is only **placed** — matched pages
       aliased, a partial tail copy-on-write'd, suffix pages allocated,
       block-table row installed (all O(1) in matched and prompt
       length) — and the slot enters the *prefilling* state;
    2. **prefill chunks** — at most ``chunks_per_step`` chunks run,
       round-robin over prefilling slots; each chunk reads every prior
       position in place through the slot's block table
       (:func:`~repro.models.transformer.prefill_chunk_paged`) and
       scatters its own K/V into the slot's pages.  The final chunk
       yields the first generated token (TTFT) and flips the slot to
       decoding;
    3. **decode** — one jitted per-slot decode step advances all
       *decoding* slots by one token (free and prefilling slots decode
       garbage that is discarded);
    4. **retire** — sequences hitting EOS or ``max_new_tokens`` are
       completed and free their slot immediately, so the *next* step's
       admission phase can refill it mid-decode of the others.

    Because chunks interleave with decode steps, a 32k prompt can no
    longer stall the decode slots for its whole prefill: decode service
    interruption per step is bounded by the chunk budget
    (``stats.decode_stall_s`` records it).  ``prefill_chunk`` sets the
    chunk size in tokens, ``"whole"`` (the default) runs one chunk per
    prompt, and ``"auto"`` makes the size a VPE axis keyed by
    prompt-length × occupancy buckets, fed from the summed per-chunk
    wall at prefill completion (compile-tainted samples dropped).
    Chunking applies to paged-resolved admissions only: a contiguous
    slot's cache stores the narrower slot dtype, so a chunk reading its
    own earlier K/V back would change numerics — the contiguous layout
    keeps the atomic copy-in path as the monolithic baseline (and
    parity anchor).

    When a ``vpe`` is supplied, each decode step is timed and fed to the
    controller under the current occupancy bucket; variant selection
    (including in-flight blind-offload trials) picks the decode-attention
    implementation, and a selection change re-jits the step.

    With ``prefix_blocks > 0`` a radix-tree shared-prefix KV cache
    (:class:`~repro.runtime.prefix_cache.PrefixCache`) sits in front of
    admission: the longest cached block-prefix of the prompt is matched
    and pinned for the request's residency, and only the suffix is
    prefilled.  Whether reuse actually beats recomputing a short prefix
    is the ``prefix_reuse`` VPE axis, measured per matched-length
    bucket.  Admission is prefix-aware: the queue's front window is
    probed against the tree and the best-matching request is admitted
    first (co-scheduling requests that share a hot prefix), bounded by
    ``max_skip`` — a request can be jumped at most that many times
    before it is forcibly next, so nothing starves.

    **KV layouts** (``kv_layout``):

    * ``"contiguous"`` — the PR 1/2 baseline: each slot owns a
      contiguous region of the per-slot cache; warm admission *copies*
      cached pages into it (O(matched length)).
    * ``"paged"`` — every slot owns a block table of page ids into the
      unified device pool shared with the prefix tree
      (:class:`~repro.runtime.page_pool.PagePool` refcounts both);
      warm admission *aliases* the cached pages (O(1) in matched
      length, copy-on-write on a partially matched tail block), a cold
      prompt's full blocks are *adopted* into the tree zero-copy, and
      decode attends through the block table.
    * ``"auto"`` — both structures are maintained and the layout of
      each admission is a VPE decision keyed by matched-length ×
      occupancy buckets, fed from measured admission + amortized decode
      wall per request (recorded at retire; samples that paid a jit
      compile are dropped).  The decode step computes both attention
      reads and selects per slot — the measurement tax of running the
      experiment online.
    """

    def __init__(self, cfg: ModelConfig, params: Any, *, slots: int = 4,
                 max_len: int = 256, vpe: Optional[VPE] = None,
                 occupancy_levels: int = 4, min_prompt_pad: int = 16,
                 prefix_blocks: int = 0, block_size: int = 16,
                 kv_layout: str = "contiguous", partial_match: bool = True,
                 max_skip: int = 4, sched_window: int = 16,
                 prefill_chunk: Any = "whole",
                 chunks_per_step: Optional[int] = None,
                 chunk_choices: Tuple[int, ...] = (128, 512, 2048),
                 decode_horizon: Any = 1,
                 horizon_choices: Tuple[int, ...] = (4, 16),
                 spec_draft: Any = "off",
                 spec_choices: Optional[Tuple[int, ...]] = None,
                 page_budget: Optional[int] = None,
                 swap: bool = False,
                 slo_weight: float = 0.0,
                 max_skip_by_class: Optional[Dict[str, int]] = None,
                 mesh_shape: Tuple[int, int] = (1, 1),
                 mesh_devices: Optional[Sequence] = None,
                 shard_dims: Optional[Tuple[int, int]] = None,
                 decode_impl: str = "auto",
                 prefill_kernel: str = "auto",
                 fault_plan: Optional[FaultPlan] = None,
                 watchdog: Any = None,
                 max_request_faults: int = 3,
                 probation_steps: int = 16,
                 max_queue_depth: Optional[int] = None) -> None:
        if not model_lib.supports_slot_serving(cfg):
            raise ValueError(f"family {cfg.family!r} has no slot-serving path")
        if kv_layout not in KV_LAYOUTS:
            raise ValueError(f"kv_layout must be one of {KV_LAYOUTS}")
        if decode_impl != "auto" and decode_impl not in kvcache.DECODE_ATTN_VARIANTS:
            raise ValueError(
                f"decode_impl must be 'auto' or one of "
                f"{sorted(kvcache.DECODE_ATTN_VARIANTS)}, got {decode_impl!r}")
        if prefill_kernel not in ("auto",) + tuple(SERVE_AXES["prefill_kernel"]):
            raise ValueError(
                f"prefill_kernel must be 'auto' or one of "
                f"{SERVE_AXES['prefill_kernel']}, got {prefill_kernel!r}")
        if isinstance(prefill_chunk, str):
            if prefill_chunk not in ("whole", "auto"):
                raise ValueError(
                    "prefill_chunk must be a token count, 'whole' or 'auto'")
        elif int(prefill_chunk) < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 = whole)")
        if chunks_per_step is not None and chunks_per_step < 1:
            raise ValueError(
                "chunks_per_step must be >= 1 (or None = adaptive)")
        if isinstance(decode_horizon, str):
            if decode_horizon != "auto":
                raise ValueError(
                    "decode_horizon must be a step count >= 1 or 'auto'")
        elif int(decode_horizon) < 1:
            raise ValueError("decode_horizon must be >= 1")
        if any(int(h) < 2 for h in horizon_choices):
            raise ValueError("horizon_choices must all be >= 2 "
                             "(1 is always the incumbent)")
        if isinstance(spec_draft, str):
            if spec_draft not in ("off", "auto"):
                raise ValueError(
                    "spec_draft must be a verify span >= 2, 'off' or 'auto'")
        elif int(spec_draft) < 2:
            raise ValueError("spec_draft must be >= 2 (a 1-position "
                             "verify is just a decode step — use 'off')")
        if spec_choices is not None and any(int(s) < 2 for s in spec_choices):
            raise ValueError("spec_choices must all be >= 2")
        self.cfg = cfg
        self.params = params
        self.num_slots = slots
        self.max_len = max_len
        self.vpe = vpe
        self.occupancy_levels = occupancy_levels
        self.min_prompt_pad = min_prompt_pad
        self.kv_layout = kv_layout
        self.partial_match = partial_match
        self.max_skip = max_skip
        self.sched_window = sched_window
        if max_skip_by_class is not None:
            bad = set(max_skip_by_class) - set(PRIORITY_CLASSES)
            if bad:
                raise ValueError(f"unknown priority classes in "
                                 f"max_skip_by_class: {sorted(bad)}")
        self.max_skip_by_class = max_skip_by_class
        self.swap = swap
        if slo_weight < 0.0:
            raise ValueError("slo_weight must be >= 0")
        self.slo_weight = slo_weight
        # -- fault tolerance (PR 10) ----------------------------------------
        # the injection plan (None in production — every hook is then one
        # None-check), the optional fence watchdog (True builds a default
        # StepWatchdog; a pre-built instance lets tests inject a clock),
        # the per-request fault budget, and the clean-span probation
        # window a demoted ladder rung must survive to re-promote
        self.faults = fault_plan
        if watchdog is True:
            watchdog = StepWatchdog()
        self.watchdog: Optional[StepWatchdog] = watchdog or None
        if max_request_faults < 1:
            raise ValueError("max_request_faults must be >= 1")
        self.max_request_faults = max_request_faults
        if probation_steps < 1:
            raise ValueError("probation_steps must be >= 1")
        self.probation_steps = probation_steps
        if max_queue_depth is not None and max_queue_depth < 1:
            raise ValueError("max_queue_depth must be >= 1")
        self.max_queue_depth = max_queue_depth
        # runtime variant demotions: ladder rung -> clean decode spans
        # still owed before re-promotion.  A demoted rung pins its safe
        # variant through the _resolve_*/_select_* resolvers; the VPE's
        # own selection state is untouched, so re-promotion is just the
        # pin lifting.
        self._demoted: Dict[str, int] = {}
        # deadline sweeps only arm once a deadline-carrying request has
        # been submitted — the common no-deadline workload pays nothing
        self._deadlines_live = False
        # -- device mesh (mp tensor shards; dp replicas live one level up) --
        # mesh_shape=(1, 1) with no explicit devices is the bitwise no-op
        # fallback: no mesh is built, nothing is device_put, dispatch keys
        # carry no shard segment — the single-device engine byte-for-byte.
        # A dp > 1 shape is the EngineReplicaGroup's job (independent
        # engines sharing one admission queue); one engine shards over mp
        # only.  ``shard_dims`` lets the group hand each replica the FULL
        # (dp, mp) for dispatch-key bucketing while the replica's own mesh
        # is its (1, mp) device row.
        dp, mp = (int(mesh_shape[0]), int(mesh_shape[1]))
        if dp < 1 or mp < 1:
            raise ValueError(f"mesh_shape axes must be >= 1, got {mesh_shape}")
        if dp > 1:
            raise ValueError(
                "a single engine replica cannot span dp > 1 — use "
                "make_serve_engine / EngineReplicaGroup for dp replicas")
        self.mesh_shape = (dp, mp)
        self._shard_dims = (tuple(int(d) for d in shard_dims)
                            if shard_dims is not None else self.mesh_shape)
        self._shard_tail: Tuple = (shard_bucket(*self._shard_dims)
                                   if self._shard_dims != (1, 1) else ())
        self.mesh = None
        if mp > 1 or mesh_devices is not None:
            self.mesh = sharding_lib.serve_mesh(dp, mp, devices=mesh_devices)
        # -- pallas capability gate (docs/kernel_variants.md ladder) --------
        # the kernel-backed variants are only offered when (1) the layout
        # has pages at all, (2) a trivial pallas_call computes the right
        # answer on this backend (a probe that raises propagates), and
        # (3) the mesh's head sharding matches the kernel's shard
        # contract (Hkv % mp == 0, so each shard sees its local head
        # slice over the full unsharded page axis).  Anything short of
        # that resolves to the gather path (_resolve_impl /
        # _resolve_kernel): a pinned "pallas" degrades where it cannot
        # apply.
        paged_capable = kv_layout in ("paged", "auto")
        self._pallas_ok = (paged_capable
                           and pallas_compat.pallas_supported()
                           and sharding_lib.kernel_shard_ok(
                               cfg.num_kv_heads, self.mesh))
        self.decode_impl = decode_impl
        self.prefill_kernel = prefill_kernel
        self.prefill_chunk = prefill_chunk
        self.chunks_per_step = chunks_per_step
        self.chunk_choices = tuple(int(c) for c in chunk_choices)
        self.decode_horizon = (decode_horizon if decode_horizon == "auto"
                               else int(decode_horizon))
        self.horizon_choices = tuple(int(h) for h in horizon_choices)
        # -- speculative decoding (fallback ladder, --decode-impl style) ----
        # A requested spec_draft resolves to "off" rather than crashing
        # when the configuration cannot host it: the verify pass writes
        # candidates through the paged block table (a contiguous-only
        # engine has none to reserve against), and speculation is a
        # variant OF the fused decode path (a decode_horizon=1 engine
        # opted out of multi-token device calls entirely).
        self.spec_draft = (spec_draft if isinstance(spec_draft, str)
                           else int(spec_draft))
        if self.spec_draft != "off" and not (
                kv_layout in ("paged", "auto") and self.decode_horizon != 1):
            self.spec_draft = "off"
        self.spec_choices = (tuple(int(s) for s in spec_choices)
                             if spec_choices is not None
                             else self.horizon_choices)
        # the draft proposer exists only on spec-enabled engines — a
        # spec-off engine pays zero host overhead at the commit sites
        self.proposer: Optional[NGramProposer] = (
            NGramProposer() if self.spec_draft != "off" else None)
        self._spec_fns: Dict[int, Callable] = {}
        # EMA of the per-call draft-acceptance fraction: the engine-level
        # workload signal quantized into the spec axis's bucket key
        # (None until the first measurement = neutral middle level).
        # Fed by real verify calls AND by the plain path's free
        # counterfactual probe (_probe_accept), so the signal stays live
        # while the axis has speculation switched off
        self._accept_ema: Optional[float] = None
        # plain-path calls between counterfactual probes: the EMA only
        # needs LIVENESS while "off" is selected, not per-token
        # precision, and a probe is order-deep table lookups per
        # committed token — sampled 1-in-4 it stays well under 1% of a
        # plain span's host share
        self._probe_every = 4
        self._probe_tick = 0
        self._spec_off_pending: Optional[Tuple[Tuple, str]] = None
        self._chunk_rr = 0           # round-robin cursor over prefilling slots
        # persistent device-side decode inputs: rebuilt from the host
        # slot mirrors only when an admission/retire/prefill-completion
        # event dirties them — a steady decode-bound step re-uploads
        # nothing (the next input token is the previous step's on-device
        # output)
        self._tok_dev = None
        self._live_dev = None
        self._use_paged_dev = None
        self._eos_dev = None
        self._masks_dirty = True
        self._fused_fns: Dict[Tuple[str, int], Callable] = {}
        self.stats = ServeStats()
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self.slots = [_Slot() for _ in range(slots)]
        self._decode_fns: Dict[str, Callable] = {}
        self._axis = "serve_decode_impl"
        self._default_variant = SERVE_AXES[self._axis][0]
        self._last_variant: Optional[str] = None
        if vpe is not None and not vpe.registry.has_op(self._axis):
            # a pinned decode_impl registers the axis as a SYSTEM op:
            # samples are still recorded per bucket under the name that
            # actually ran, but the controller never trials alternatives
            # (the bench's fixed-arm pattern, now first-class)
            vpe.registry.register_op(self._axis,
                                     system=(decode_impl != "auto"))
            # kernel-backed variants are only offered past the
            # capability gate — an engine that would resolve them to
            # the gather path anyway must not trial them as if distinct
            names = [n for n in SERVE_AXES[self._axis]
                     if self._pallas_ok or n not in kvcache.PAGED_KERNEL_IMPLS]
            for i, name in enumerate(names):
                vpe.registry.register_variant(
                    self._axis, name, fn=(lambda name=name: name), default=(i == 0))
        if vpe is not None and self.decode_horizon == "auto" \
                and not vpe.registry.has_op("decode_horizon"):
            # "1" (the classic one-token step) is the incumbent; the
            # fused horizons from this engine's horizon_choices are the
            # blind-offload candidates, trialed per queue-depth ×
            # occupancy bucket and fed from per-token wall time
            vpe.registry.register_op("decode_horizon")
            names = ["1"] + [str(h) for h in self.horizon_choices]
            for i, name in enumerate(names):
                vpe.registry.register_variant(
                    "decode_horizon", name, fn=(lambda name=name: name),
                    default=(i == 0))
        if vpe is not None and self.spec_draft == "auto" \
                and not vpe.registry.has_op("spec_draft"):
            # "off" (the plain fused-horizon path) is the incumbent and
            # the verify spans from spec_choices are the candidates,
            # trialed per queue-depth × occupancy × accept-level bucket
            # and fed from per-committed-token wall of the full span —
            # so the controller backs off to plain horizons exactly
            # where the measured accept rate stops paying for the wider
            # verify pass
            vpe.registry.register_op("spec_draft")
            names = ["off"] + [str(s) for s in self.spec_choices]
            for i, name in enumerate(names):
                vpe.registry.register_variant(
                    "spec_draft", name, fn=(lambda name=name: name),
                    default=(i == 0))
        # -- KV storage (layout-dependent) ---------------------------------
        self.block_size = block_size
        paged_capable = kv_layout in ("paged", "auto")
        if paged_capable and max_len % block_size:
            raise ValueError(
                f"paged layouts need max_len ({max_len}) divisible by "
                f"block_size ({block_size}) — equal column counts are what "
                f"keep the two layouts' decode attention bit-identical")
        self.nb_max = max_len // block_size if paged_capable else 0
        self.pages: Optional[PagePool] = None
        self.page_pool = None
        if page_budget is not None and not paged_capable:
            raise ValueError("page_budget only applies to paged/auto layouts")
        if paged_capable:
            # sized so the engine can never deadlock on pages: worst-case
            # live block tables (x2 in auto mode, where contiguous
            # admissions also pin tree blocks that no table owns) plus one
            # possible pinned partial block per slot, plus the requested
            # cached-prefix headroom
            n_pages = (slots * self.nb_max * (2 if kv_layout == "auto" else 1)
                       + slots + max(prefix_blocks, 0))
            if page_budget is not None:
                # over-pressure operation: run with FEWER pages than the
                # worst case and recover by eviction + preemption instead
                # of raising.  Floor: one max_len residency must fit in
                # an otherwise-drained pool (nb_max table pages + a
                # pinned partial original + its COW clone), or a single
                # request could never complete no matter what is
                # preempted — the one genuinely unrecoverable sizing
                floor = self.nb_max + 2
                if page_budget < floor:
                    raise ValueError(
                        f"page_budget={page_budget} below the minimum "
                        f"{floor} (= max_len/block_size + 2) a single "
                        f"request needs to make progress")
                n_pages = page_budget
            self.pages = PagePool(n_pages)
            self.page_pool = self._alloc(
                lambda: model_lib.init_page_pool(cfg, n_pages, block_size))
            self._gather_pages = jax.jit(kvcache.gather_pages)
            self._write_pages = jax.jit(kvcache.write_pages, donate_argnums=0)
            # preemption swap: gather a victim's filled pages to host /
            # scatter them back into fresh pages at re-admission
            self._swap_gather = jax.jit(kvcache.swap_out_pages)
            self._swap_scatter = jax.jit(kvcache.swap_in_pages,
                                         donate_argnums=0)
            self._copy_page = jax.jit(kvcache.copy_page, donate_argnums=0)
            self._admit_paged = jax.jit(self._admit_paged_fn, donate_argnums=0)
            self._set_bt = jax.jit(self._set_bt_fn, donate_argnums=0)
            self._set_bt_many = jax.jit(self._set_bt_many_fn, donate_argnums=0)
            self._set_len = jax.jit(self._set_len_fn, donate_argnums=0)
            # the chunked-prefill jits, one per chunk-attention backend
            # (the prefill_kernel axis): donate the pool so every chunk's
            # page scatter updates it in place; one specialization per
            # padded chunk shape (power-of-two buckets) per backend.
            # Built lazily via _prefill_chunk_fn so an engine that never
            # selects "pallas" never traces it.
            self._prefill_chunks: Dict[str, Callable] = {}
        if kv_layout == "paged":
            self.cache = self._alloc(lambda: model_lib.init_paged_cache(
                cfg, slots, max_len, block_size, self.pages.trash_id))
        elif kv_layout == "auto":
            self.cache = self._alloc(lambda: {
                **model_lib.init_slot_cache(cfg, slots, max_len),
                "bt": model_lib.init_paged_cache(
                    cfg, slots, max_len, block_size, self.pages.trash_id)["bt"],
            })
        else:
            self.cache = self._alloc(
                lambda: model_lib.init_slot_cache(cfg, slots, max_len))
        self._prefill = jax.jit(
            lambda p, t, n: model_lib.prefill_slot_kv(cfg, p, t, n))
        # the old cache is dead after every insert — donate it so XLA
        # updates the slot pages in place instead of copying the pool
        self._insert = jax.jit(
            lambda c, k, v, s, n: model_lib.insert_slot_kv(c, k, v, s, n),
            donate_argnums=0)
        if vpe is not None and kv_layout == "auto" \
                and not vpe.registry.has_op("kv_layout"):
            vpe.registry.register_op("kv_layout")
            for i, name in enumerate(SERVE_AXES["kv_layout"]):
                vpe.registry.register_variant(
                    "kv_layout", name, fn=(lambda name=name: name),
                    default=(i == 0))
        if vpe is not None and paged_capable and prefill_chunk == "auto" \
                and not vpe.registry.has_op("prefill_chunk"):
            # variant names come from this engine's chunk_choices; the
            # incumbent is "whole" (one chunk — the PR 3 behavior) and
            # the controller blind-trials the fixed sizes per bucket
            vpe.registry.register_op("prefill_chunk")
            names = ["whole"] + [str(c) for c in self.chunk_choices]
            for i, name in enumerate(names):
                vpe.registry.register_variant(
                    "prefill_chunk", name, fn=(lambda name=name: name),
                    default=(i == 0))
        if vpe is not None and self._pallas_ok and prefill_kernel == "auto" \
                and not vpe.registry.has_op("prefill_kernel"):
            # the chunk-attention backend axis: "gather" (incumbent, the
            # in-jit linearization) vs "pallas" (block-indirect kernel),
            # keyed by the same prompt-length × occupancy bucket as
            # prefill_chunk and fed from the same clean chunk walls.
            # Only registered past the capability gate — otherwise every
            # admission resolves to "gather" with no measurement to run.
            vpe.registry.register_op("prefill_kernel")
            for i, name in enumerate(SERVE_AXES["prefill_kernel"]):
                vpe.registry.register_variant(
                    "prefill_kernel", name, fn=(lambda name=name: name),
                    default=(i == 0))
        # -- shared-prefix KV cache (radix tree) ---------------------------
        self.prefix_cache: Optional[PrefixCache] = None
        if prefix_blocks > 0:
            if paged_capable:
                # ONE id space: tree pages and live block tables draw from
                # (and refcount against) the same pool
                self.prefix_cache = PrefixCache(
                    self.pages.num_pages, block_size, pool=self.pages)
            else:
                self.prefix_cache = PrefixCache(prefix_blocks, block_size)
                # pages live in the COMPUTE dtype so a warm suffix prefill
                # sees bit-identical prefix K/V to a cold full prefill
                self.block_pool = self._alloc(lambda: kvcache.init_block_pool(
                    prefix_blocks, cfg.num_layers, cfg.num_kv_heads,
                    block_size, cfg.head_dim, dtype=jnp.dtype(cfg.dtype)))
                self._gather = jax.jit(kvcache.gather_blocks)
                self._write_block = jax.jit(
                    lambda pool, k, v, bid, st: kvcache.write_block(
                        pool, k, v, bid, st, block_size),
                    donate_argnums=0)
            self._insert_at = jax.jit(
                lambda c, k, v, s, st, n: model_lib.insert_slot_kv_at(
                    c, k, v, s, st, n),
                donate_argnums=0)
            self._prefill_suffix = jax.jit(
                lambda p, t, pk, pv, pl, tl: model_lib.prefill_suffix_kv(
                    cfg, p, t, pk, pv, pl, tl))
            if vpe is not None and not vpe.registry.has_op("prefix_reuse"):
                vpe.registry.register_op("prefix_reuse")
                for i, name in enumerate(SERVE_AXES["prefix_reuse"]):
                    vpe.registry.register_variant(
                        "prefix_reuse", name, fn=(lambda name=name: name),
                        default=(i == 0))
        if self.mesh is not None:
            self._place_params()

    # -- mesh placement ------------------------------------------------------
    def _alloc(self, make: Callable[[], Any]) -> Any:
        """Create a KV container where it lives.  On a mesh it is made
        under one jit whose ``out_shardings`` are its
        :func:`~repro.distributed.sharding.serve_cache_specs` — ``k``/
        ``v`` split on ``Hkv`` over ``mp``, block tables and lengths
        replicated — so no device ever holds more than its shard.
        Without a mesh it lands on the default device."""
        if self.mesh is None:
            return make()
        specs = sharding_lib.serve_cache_specs(jax.eval_shape(make),
                                               self.mesh)
        return jax.jit(make, out_shardings=self._named(specs))()

    def _named(self, specs: Any) -> Any:
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def _place_params(self) -> None:
        """Move each parameter leaf that is not already where the
        rule table puts it on this engine's mesh (heads / ffn hidden /
        vocab on ``mp``).  Parameters made by
        ``model.init_params(cfg, rng, mesh)`` for the same mesh, or on
        the one device of a ``(1, 1)`` replica row, stay where they are:
        nothing is copied.  All later engine jits see committed inputs
        and GSPMD propagates the shardings through them."""
        target = self._named(sharding_lib.param_specs(self.params, self.mesh))

        def put(x, s):
            if isinstance(x, jax.Array) and x.sharding.is_equivalent_to(
                    s, x.ndim):
                return x
            return jax.device_put(x, s)

        self.params = jax.tree.map(put, self.params, target)

    # -- small jitted paged-state updates ----------------------------------
    @staticmethod
    def _admit_paged_fn(cache, row, slot, true_len):
        out = dict(cache)
        out["bt"] = cache["bt"].at[slot].set(row)
        out["length"] = cache["length"].at[slot].set(true_len)
        return out

    @staticmethod
    def _set_bt_fn(cache, slot, col, pid):
        out = dict(cache)
        out["bt"] = cache["bt"].at[slot, col].set(pid)
        return out

    @staticmethod
    def _set_bt_many_fn(cache, slots, cols, pids):
        """Batched block-table splice: every (slot, col) <- pid in ONE
        scatter (arrays padded by repeating a real splice — duplicate
        identical writes are harmless), replacing the one-jit-call-per-
        page loop the pre-horizon engine paid on every block boundary."""
        out = dict(cache)
        out["bt"] = cache["bt"].at[slots, cols].set(pids)
        return out

    @staticmethod
    def _set_len_fn(cache, slot, n):
        out = dict(cache)
        out["length"] = cache["length"].at[slot].set(n)
        return out

    # -- request intake ----------------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue a request — or terminally fail it.

        A request the engine can never serve (prompt + budget exceeding
        slot capacity, an empty prompt, an unknown priority class) is
        NOT an engine error: it completes immediately with
        ``status="failed"`` and a per-request ``error``, exactly like a
        served request completes with ``status="done"`` — one request's
        bad parameters must not throw at a caller batching thousands.
        (Empty prompts additionally used to poison the prefix-aware
        scheduler: probing with ``max_match=len(prompt)-1 == -1`` is a
        no-limit probe.)"""
        req.submit_t = time.perf_counter()
        err = _intake_error(req, self.max_len)
        if err is not None:
            self._fail_request(req, "intake", err)
            return
        if self.max_queue_depth is not None \
                and len(self.queue) >= self.max_queue_depth:
            # admission-bound shedding: refuse load while it is still a
            # host-side queue entry, BEFORE it can compete for pages and
            # preempt resident work — the cheap rung of overload control
            self._fail_request(
                req, "capacity",
                f"queue depth {len(self.queue)} at admission bound "
                f"{self.max_queue_depth}")
            return
        if req.deadline_s is not None:
            self._deadlines_live = True
        req.status = "queued"
        self.queue.append(req)

    def _fail_request(self, req: Request, reason: str, detail: str,
                      slot: Optional[int] = None) -> None:
        """Terminally fail a request — the ONE failure path, intake
        through mid-flight: reason code + human detail recorded,
        completed immediately, the engine keeps serving.

        The failed request gets the same terminal accounting as a served
        one: ``done_t`` is stamped, a never-admitted failure records its
        (terminal) queue wait on the REQUEST, and an admitted one keeps
        the queue wait its admission already recorded — closing the PR 7
        gap where a mid-flight failure lacked ``done_t``.  The
        engine-level ``stats.queue_wait_s`` series stays
        admitted-requests-only, and ``rejected`` counts the
        never-admitted subset, so ``submitted == len(stats.queue_wait_s)
        + stats.rejected`` once drained; the full failed population is
        :attr:`ServeStats.failed_requests` via the per-reason counters.

        ``slot`` detaches an in-flight residency first: pages released
        (every page a slot owns is refcounted, so release is the exact
        rollback of its reservations), prefix pin dropped, proposer
        context forgotten, device masks dirtied."""
        assert reason in FAIL_REASONS, reason
        if slot is not None:
            s = self.slots[slot]
            if s.layout == "paged" and s.pages:
                self._release_slot_pages(slot)
            s.req = None
            s.prefilling = False
            s.fill_pos = 0
            s.pos = 0
            s.chunk_walls = []
            s.chunk_costs = []
            s.reuse_bucket = None
            s.chunk_bucket = None
            s.kernel_bucket = None
            s.admit_bucket = None
            if self.proposer is not None:
                self.proposer.forget_slot(slot)
            self._masks_dirty = True
        if req.cache_handle is not None:
            self.prefix_cache.release(req.cache_handle)
            req.cache_handle = None
        req.swap = None
        req.error = reason
        req.error_detail = detail
        req.status = "failed"
        req.done = True
        req.done_t = time.perf_counter()
        if req.admit_step < 0:
            req.queue_wait_s = req.done_t - req.submit_t
            self.stats.rejected += 1
        else:
            req.done_step = self.stats.decode_steps
        self.stats.failed_by_reason[reason] = \
            self.stats.failed_by_reason.get(reason, 0) + 1
        self.completed.append(req)

    def _requeue(self, req: Request) -> None:
        """Put a rolled-back or preempted request back in the queue:
        ahead of its own class, behind every strictly better class.

        A plain ``insert(0, ...)`` would park e.g. a batch-class request
        whose placement rolled back in FRONT of waiting interactive
        requests — each interactive admission would then "jump" it,
        ticking its ``skips`` until the starvation bound forced it ahead
        of traffic that should outrank it (a priority inversion the
        request never earned; rollback is the ENGINE's doing, not the
        queue's).  Inserting at the head of its own class restores its
        pre-admission position relative to its peers without charging
        anyone a skip."""
        rank = PRIORITY_RANK[req.priority]
        pos = next((j for j, r in enumerate(self.queue)
                    if PRIORITY_RANK[r.priority] >= rank), len(self.queue))
        self.queue.insert(pos, req)

    @property
    def num_active(self) -> int:
        """Occupied slots — decoding AND mid-prefill (run() drains both)."""
        return sum(1 for s in self.slots if not s.free)

    @property
    def num_decoding(self) -> int:
        """Slots past their prefill: the decode step's real batch."""
        return sum(1 for s in self.slots
                   if s.req is not None and not s.prefilling)

    # -- page accounting ----------------------------------------------------
    def _alloc_page(self, *, exclude: Optional[int] = None,
                    rank: Optional[int] = None,
                    decode_growth: bool = False) -> int:
        """Take a page from the shared pool, escalating under pressure.

        The escalation ladder (each rung only when the previous is dry):

        1. the free list;
        2. evict an unpinned cached prefix (the PR 2/3 behavior);
        3. **preempt a victim slot** — the lowest-priority/youngest
           prefilling slot whose pages (or released pins) can feed the
           pool; for ``decode_growth`` (a decoding slot needs its next
           block NOW — mid-horizon there is no host to wait) the
           ladder extends to equal-priority prefills and then to
           strictly-lower-priority *decoding* slots.

        ``exclude`` shields the slot the allocation is FOR; ``rank`` is
        the requesting request's priority rank (a request never
        preempts its equals or betters, except the decode-growth rung).
        When the whole ladder is dry, :class:`_PagePressure` is raised
        for the CALLER to recover from — placement rolls back
        all-or-nothing and requeues, decode growth preempts the growing
        slot itself.  Nothing escapes the engine.

        An injected ``page_alloc`` device fault raises
        :class:`SimulatedFault` here instead: the same callers own the
        same rollback obligations (placement re-uses its all-or-nothing
        unref; growth/admit charge the requesting request's fault
        budget), so allocation faults prove the rollback paths against
        a failure :class:`_PagePressure` cannot model — one that
        retrying/preempting harder will not fix."""
        fault = self._take_fault("page_alloc")
        if fault is not None:
            raise SimulatedFault(
                f"injected page-pool allocation fault at call "
                f"#{self.faults.calls['page_alloc'] - 1}")
        pid = self.pages.alloc()
        while pid is None:
            if self.prefix_cache is not None and self.prefix_cache.evict(1):
                pid = self.pages.alloc()
                continue
            victim = self._pick_victim(exclude, rank, decode_growth)
            if victim is None:
                raise _PagePressure(
                    "page demand exceeds free list + evictable prefixes "
                    "+ preemptible slots")
            self._preempt_slot(victim)
            pid = self.pages.alloc()
        return pid

    def _skip_budget(self, req: Request) -> int:
        """Per-class starvation bound (uniform ``max_skip`` fallback)."""
        if self.max_skip_by_class is not None:
            return self.max_skip_by_class.get(req.priority, self.max_skip)
        return self.max_skip

    def _queue_pressure(self) -> float:
        """Class-weighted count of QUEUED requests — the second term of
        the scheduler objective.  Every request waiting in the queue
        pays out the full wall time of whatever long device call (fused
        horizon, prefill chunk) the engine commits to next, so that
        call's *charged* cost is ``wall x (1 + slo_weight x pressure)``:
        cheap when nobody urgent waits, expensive when interactive
        requests are stacking up."""
        return sum(SLO_CLASS_WEIGHT.get(r.priority, 1.0)
                   for r in self.queue)

    def _slo_bucket(self) -> Tuple:
        """Queue-composition bucket concatenated onto the horizon/chunk
        dispatch keys when SLO-aware scheduling is on."""
        ni = sum(1 for r in self.queue if r.priority == "interactive")
        return slo_pressure_bucket(ni, len(self.queue) - ni)

    def _pick_victim(self, exclude: Optional[int], rank: Optional[int],
                     decode_growth: bool) -> Optional[int]:
        """Choose a slot to preempt for pages, or None.

        Prefilling victims strictly before decoding ones (a paused
        prefill loses only re-placeable work; a paused decode loses its
        residency).  Within a tier: lowest priority class first, then
        youngest (latest-admitted) — the least sunk work.  Slots that
        could free nothing (no pages, no pin to release) are never
        picked."""
        r = len(PRIORITY_CLASSES) if rank is None else rank
        prefills: List[Tuple[int, int, int, int]] = []
        decodes: List[Tuple[int, int, int, int]] = []
        for j, s in enumerate(self.slots):
            if j == exclude or s.req is None:
                continue
            if not s.pages and s.req.cache_handle is None:
                continue            # frees nothing: pointless victim
            vr = PRIORITY_RANK[s.req.priority]
            if s.prefilling:
                if vr > r or (decode_growth and vr >= r):
                    prefills.append((vr, s.req.admit_step, -s.fill_pos, j))
            elif decode_growth and vr > r:
                decodes.append((vr, s.req.admit_step, -len(s.req.out), j))
        for tier in (prefills, decodes):
            if tier:
                return max(tier)[3]
        return None

    def _preempt_slot(self, j: int) -> None:
        """Preempt slot ``j``: capture resumable state, return its pages
        to the pool, unpin its prefix path, requeue its request at the
        head of its priority class (``status="preempted"``,
        :meth:`_requeue`).

        With ``swap=True`` the filled pages' K/V is gathered to host
        first (:meth:`_swap_out`) so re-admission scatters it back
        instead of recomputing; either way a preempted DECODING slot
        resumes exactly via its :meth:`Request.effective_prompt` —
        greedy decode is deterministic, so re-prefilling prompt+emitted
        and decoding on reproduces the un-preempted stream."""
        slot = self.slots[j]
        req = slot.req
        was_decoding = not slot.prefilling
        if self.swap and slot.layout == "paged":
            filled = slot.pos if was_decoding else slot.fill_pos
            if filled > 0:
                self._swap_out(j, filled)
        if slot.layout == "paged" and slot.pages:
            if req.swap is not None:
                self.pages.swap_out(slot.pages)
                slot.pages = []
            else:
                self._release_slot_pages(j)
        if req.cache_handle is not None:
            self.prefix_cache.release(req.cache_handle)
            req.cache_handle = None
        req.preemptions += 1
        req.status = "preempted"
        self.stats.preemptions += 1
        if was_decoding:
            self.stats.decode_preemptions += 1
        slot.req = None
        slot.prefilling = False
        slot.fill_pos = 0
        slot.pos = 0
        slot.chunk_walls = []
        slot.chunk_costs = []
        slot.reuse_bucket = None
        slot.chunk_bucket = None
        slot.admit_bucket = None
        if self.proposer is not None:
            # drop the rolling draft context only — table entries are
            # the cross-request memory and stay; re-admission re-seeds
            # the context from effective_prompt
            self.proposer.forget_slot(j)
        self._requeue(req)
        self._masks_dirty = True

    def _swap_out(self, j: int, filled: int) -> None:
        """Gather slot ``j``'s filled K/V to host memory before its
        pages are given away (page count padded to a power of two by
        repeating the first id — bounded jit shapes; the real extent
        travels with the record as ``filled``)."""
        slot = self.slots[j]
        bs = self.block_size
        nb = -(-filled // bs)                       # ceil
        ids = slot.pages[:nb]
        nb_pad = pad_to_bucket(nb, minimum=1)
        ids_pad = np.asarray(ids + [ids[0]] * (nb_pad - nb), np.int32)
        k, v = self._swap_gather(self.page_pool, jnp.asarray(ids_pad))
        # np.asarray fences AND copies off-device: this IS the swap
        slot.req.swap = (np.asarray(k), np.asarray(v), filled)
        self.stats.swap_outs += 1
        self.stats.swapped_pages += nb

    def _swap_in_slot(self, i: int) -> int:
        """Scatter a swap-resumed request's host K/V into the pages its
        re-placement just allocated; returns the resume fill position
        (the preempted residency's ``filled``) so chunked prefill picks
        up exactly where the victim stopped instead of recomputing."""
        slot = self.slots[i]
        req = slot.req
        k, v, filled = req.swap
        bs = self.block_size
        nb = -(-filled // bs)                       # ceil
        ids = slot.pages[:nb]
        nb_pad = k.shape[3] // bs                   # gather-time padding
        trash = self.pages.trash_id
        ids_pad = np.asarray(ids + [trash] * (nb_pad - nb), np.int32)
        # padded starts are negative: their whole source window is
        # invalid, so write_pages keeps the trash row's old content
        starts = [b * bs for b in range(nb)]
        starts_pad = np.asarray(starts + [-bs] * (nb_pad - nb), np.int32)
        self.page_pool = self._swap_scatter(
            self.page_pool, jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(ids_pad), jnp.asarray(starts_pad), jnp.int32(filled))
        req.swap = None
        self.stats.swap_ins += 1
        return filled

    def check_kv(self) -> None:
        """Cross-structure page audit: pool refcounts must be exactly
        accounted for by tree ownership + live block tables (and the
        tree's own structural invariants must hold).  Raises
        AssertionError on any leak or dangling reference."""
        if self.prefix_cache is not None:
            self.prefix_cache.check()
        if self.pages is None:
            return
        owners: Dict[int, int] = {}
        if self.prefix_cache is not None:
            for pid in self.prefix_cache.owned_pages():
                owners[pid] = owners.get(pid, 0) + 1
        for s in self.slots:
            for pid in s.pages:
                owners[pid] = owners.get(pid, 0) + 1
        self.pages.check(owners)

    # -- fault tolerance: the recovery ladder (PR 10) ------------------------
    # A faulted span quarantines the VARIANT before the engine: each
    # ladder rung names a dispatch decision with a known-safe bottom
    # (docs/kernel_variants.md, docs/speculative_decoding.md fallback
    # ladders, now usable as runtime demotions):
    #   decode_pallas  — kernel-backed decode attention -> grouped/gather
    #   prefill_pallas — block-indirect chunk attention -> gather
    #   spec           — speculative verify             -> off
    #   horizon        — fused multi-token calls        -> 1 step/call
    # A demotion pins the safe variant through the _resolve_*/_select_*
    # resolvers for ``probation_steps`` clean decode spans, then lifts —
    # the VPE's measured selection state is never touched, so
    # re-promotion costs nothing and the axis resumes exactly where the
    # fault interrupted it.

    def _take_fault(self, site: str):
        """The injection hook: one per-site plan lookup (None without a
        plan — production pays a single attribute check per span)."""
        return self.faults.take(site) if self.faults is not None else None

    def _rung_demoted(self, rung: str) -> bool:
        return rung in self._demoted

    def _demote(self, rung: str) -> None:
        """Quarantine a ladder rung for a fresh probation window (a
        repeat fault refreshes the window without recounting the
        demotion).  Demoting the prefill kernel also re-resolves slots
        already mid-prefill — their NEXT chunk must not re-run the
        faulted backend."""
        if rung not in self._demoted:
            self.stats.demotions[rung] = self.stats.demotions.get(rung, 0) + 1
        self._demoted[rung] = self.probation_steps
        if rung == "prefill_pallas":
            for s in self.slots:
                if s.prefilling and s.kernel in kvcache.PAGED_KERNEL_IMPLS:
                    s.kernel = "gather"
                    s.kernel_bucket = None      # mixed-backend walls: drop

    def _tick_probation(self) -> None:
        """One CLEAN decode span survived (no fault taken, no watchdog
        trip): every demoted rung's probation counts down; at zero the
        pin lifts and the variant is eligible again (VPE re-promotion —
        the controller's selection was never overwritten)."""
        for rung in list(self._demoted):
            self._demoted[rung] -= 1
            if self._demoted[rung] <= 0:
                del self._demoted[rung]
                self.stats.repromotions += 1

    def _charge_fault(self, req: Request) -> bool:
        """Charge one fault against the request's budget; True means the
        budget is spent and the request must fail terminally."""
        req.faults += 1
        return req.faults >= self.max_request_faults

    def _numeric_fault(self, i: int, detail: str) -> None:
        """Slot-level quarantine for poisoned logits: everything this
        span wrote to the slot's KV is untrusted (garbage K/V can land
        under any NaN logit), so the slot is preempted WITHOUT swap —
        swap would faithfully preserve the poison — and the request
        resumes by recomputing clean KV from
        :meth:`Request.effective_prompt` (only validated tokens were
        ever committed to ``out``).  Poisoned decode writes land only in
        the slot's private tail pages (aliased tree pages are read-only
        to decode; the first writable block is COW-cloned at admission),
        so releasing the slot's pages discards every tainted byte.  A
        request whose fault budget is spent fails terminally instead."""
        slot = self.slots[i]
        req = slot.req
        self.stats.numeric_faults += 1
        if self._charge_fault(req):
            self._fail_request(req, "numeric_fault", detail, slot=i)
            return
        swap_save, self.swap = self.swap, False
        try:
            self._preempt_slot(i)
        finally:
            self.swap = swap_save

    def _span_device_fault(self, rung: Optional[str],
                           touched: Sequence[int], detail: str) -> None:
        """Recover from a device fault at a decode-span boundary.  The
        fault fires BEFORE dispatch (the decode/fused/spec jits donate
        pool + cache, so a post-call fault would leave consumed buffers
        — that failure mode is the replica group's job), which means
        engine state is intact and the touched slots simply retry next
        step.

        Ladder: blame the VARIANT first — demote ``rung`` and retry.
        When there is no rung left to blame (the fault hit the safe
        bottom variant, or the rung was already demoted), charge the
        touched requests' fault budgets and terminally fail the
        exhausted ones.  Either way reserved-but-unwritten horizon pages
        roll back, so the pool stays audit-clean."""
        self.stats.device_faults += 1
        variant_blamed = rung is not None and not self._rung_demoted(rung)
        if rung is not None:
            self._demote(rung)
        for i in list(touched):
            slot = self.slots[i]
            if slot.req is None or slot.prefilling:
                continue
            if not variant_blamed and self._charge_fault(slot.req):
                self._fail_request(slot.req, "device_fault", detail, slot=i)
            elif slot.layout == "paged":
                self._rollback_reserved(i)

    def _guarded_fence(self, value, fault) -> Tuple[np.ndarray, bool]:
        """Fence a decode span's token output, through the watchdog when
        one is armed; returns ``(host_value, tripped)``.

        A trip — injected ``stall`` or a real fence overshooting the
        EWMA budget — does NOT discard the span: the value arrived, just
        late, so the caller commits it and demotes the variant that
        stalled.  :meth:`StepWatchdog.guard` raises with the fence
        already drained, so the post-raise ``np.asarray`` is a cheap
        host conversion, not a second wait."""
        if fault is not None and fault.kind == "stall":
            # planned stall: count the trip without wall-clock sleeping
            # (the EWMA budget is real time; tests must stay fast)
            if self.watchdog is not None:
                self.watchdog.trips += 1
            self.stats.watchdog_trips += 1
            return np.asarray(value), True
        if self.watchdog is not None:
            try:
                return np.asarray(self.watchdog.guard(value)), False
            except StragglerTimeout as e:
                self.stats.watchdog_trips += 1
                return np.asarray(value), True
        return np.asarray(value), False

    def _deadline_expired(self, req: Request, now: float) -> bool:
        return (req.deadline_s is not None
                and now - req.submit_t > req.deadline_s)

    def _shed_expired(self) -> None:
        """Deadline enforcement sweep (armed only once a deadline-
        carrying request exists): expired QUEUED requests shed host-side
        and expired RUNNING slots stop burning decode steps on answers
        nobody is waiting for.  Runs at the top of :meth:`step` — which
        is also every fused-call boundary, so a deadline expiring
        mid-residency is honored at the next span edge."""
        if not self._deadlines_live:
            return
        now = time.perf_counter()
        expired = [r for r in self.queue if self._deadline_expired(r, now)]
        if expired:
            self.queue = [r for r in self.queue
                          if not self._deadline_expired(r, now)]
            for r in expired:
                self._fail_request(
                    r, "deadline",
                    f"expired in queue after {now - r.submit_t:.3f}s "
                    f"(deadline {r.deadline_s:.3f}s)")
        for i, s in enumerate(self.slots):
            if s.req is not None and not s.req.canary \
                    and self._deadline_expired(s.req, now):
                self._fail_request(
                    s.req, "deadline",
                    f"expired after {now - s.req.submit_t:.3f}s resident "
                    f"(deadline {s.req.deadline_s:.3f}s)", slot=i)

    # -- prefix-aware admission scheduling ----------------------------------
    def _pop_next(self) -> Request:
        """Pick the next request to admit.

        Priority class first, prefix affinity second: the front
        ``sched_window`` entries are narrowed to the best class present
        (interactive jumps batch), then probed against the tree (cheap
        host-side walk, no pinning) and the longest match wins, so
        requests sharing a hot cached prefix are co-scheduled while it
        is resident (ROADMAP: raises hit rate under mixed tenant
        traffic).  Starvation bound: every time a request is jumped its
        ``skips`` counter ticks; any request that has been skipped its
        class's ``max_skip`` times (``max_skip_by_class``, uniform
        ``max_skip`` otherwise) is admitted before anything may jump
        the queue again, so the wait of request i is bounded by
        ``(max_skip + 1) * (i + 1)`` admissions — priority raises who
        goes FIRST, never how long anyone can be left behind.
        """
        if len(self.queue) == 1:
            return self.queue.pop(0)
        # starvation bound, now per-class: any request that has been
        # jumped its class's ``max_skip`` times is admitted before
        # anything may jump the queue again.  With one uniform budget
        # skip counts are monotone non-increasing along the queue
        # (jumping position j increments EVERY request ahead of j, and
        # new arrivals join the tail at 0) and the head is the only
        # possible exhaustee; per-class budgets break the monotonicity
        # argument, so the scan takes the FRONT-MOST exhausted request —
        # which preserves the wait bound: position i is jumped at most
        # budget(i) times, and each requester ahead of it is admitted at
        # most budget+1 times before i, so i admits within
        # ``(max_skip + 1) * (i + 1)`` admissions (max_skip = its
        # class's budget when uniform, the largest configured budget
        # when mixed).
        forced = next((j for j, r in enumerate(self.queue)
                       if r.skips >= self._skip_budget(r)), None)
        if forced is not None:
            for r in self.queue[:forced]:
                r.skips += 1
            self.stats.sched_skips += forced
            return self.queue.pop(forced)
        window = self.queue[:self.sched_window]
        ranks = [PRIORITY_RANK[r.priority] for r in window]
        best_rank = min(ranks)
        if self.prefix_cache is None:
            # no prefix affinity to weigh: front-most best-class request
            best = ranks.index(best_rank)
        else:
            # among the window's best class only: longest cached prefix
            best, best_len = None, -1
            for j, r in enumerate(window):
                if ranks[j] != best_rank:
                    continue
                m = self.prefix_cache.probe(r.prompt,
                                            max_match=len(r.prompt) - 1)
                if m > best_len:
                    best, best_len = j, m
        for r in self.queue[:best]:
            r.skips += 1
        self.stats.sched_skips += best
        return self.queue.pop(best)

    # -- engine internals --------------------------------------------------
    def _admit(self) -> None:
        while self.queue:
            # re-scan each time: a request finishing at prefill (e.g.
            # max_new_tokens == 1) frees its slot for the next in queue
            i = next((j for j, s in enumerate(self.slots) if s.free), None)
            if i is None:
                return
            slot = self.slots[i]
            req = self._pop_next()
            now = time.perf_counter()
            if self._deadline_expired(req, now):
                # expired while queued: shed at the admission edge
                # instead of spending placement + prefill on it
                self._fail_request(
                    req, "deadline",
                    f"expired in queue after {now - req.submit_t:.3f}s "
                    f"(deadline {req.deadline_s:.3f}s)")
                continue
            if req.admit_step < 0:
                # first admission only: a preempted request keeps its
                # original queue-wait/admit-step record — the soak
                # invariants are per request, not per residency
                req.admit_step = self.stats.decode_steps
                req.queue_wait_s = now - req.submit_t
                self.stats.queue_wait_s.append(req.queue_wait_s)
            req.status = "running"
            # a preempted-and-requeued DECODING request resumes by
            # prefilling prompt + already-emitted tokens (greedy decode
            # is deterministic, so the continuation is exact)
            prompt = req.effective_prompt()
            S = len(prompt)
            occ = self.num_active           # occupancy excluding this slot
            matched = 0
            if self.prefix_cache is not None:
                # never match the full prompt: the prefill must still
                # produce the first generated token's logits.  Partial
                # tail matching is paged-only — the contiguous layout
                # copies whole blocks and cannot alias half of one
                # copy-on-write.  A swap-resume matches NOTHING: its
                # swapped K/V covers the filled range wholesale and must
                # not be spliced with tree pages whose extent may have
                # changed while the request was preempted.
                allow_partial = (self.partial_match
                                 and self.kv_layout in ("paged", "auto"))
                req.cache_handle = self.prefix_cache.acquire(
                    prompt, max_match=(0 if req.swap is not None else S - 1),
                    allow_partial=allow_partial and req.swap is None)
                matched = req.cache_handle.matched_len
                self.stats.prefix_lookups += 1
            # the layout decision sees the RAW match (what aliasing could
            # use); hit accounting and the prefix_reuse axis see only what
            # the chosen layout can actually reuse — an auto admission
            # that resolves a partial-only match to the contiguous layout
            # reuses nothing and must neither count as a hit nor feed a
            # cold full-prefill wall time into the "reuse" samples
            if req.swap is not None:
                # swapped K/V only scatters back into a block table
                layout, lbucket = "paged", None
            else:
                layout, lbucket = self._select_layout(matched)
            use_matched = (matched if layout == "paged"
                           else self.block_size * len(req.cache_handle.nodes)
                           if req.cache_handle is not None else 0)
            variant, rbucket = "reuse", None
            if use_matched:
                self.stats.prefix_hits += 1
                if self.vpe is not None:
                    rbucket = prefix_len_bucket(use_matched)
                    variant = self.vpe.controller.select("prefix_reuse",
                                                         rbucket)
            slot.req = req
            slot.layout = layout
            slot.admit_bucket = lbucket
            slot.tainted = False
            if layout == "paged":
                # placement only — the prompt's compute runs as chunks
                # interleaved with decode steps (:meth:`_run_prefill_chunks`)
                try:
                    self._place_paged(i, req,
                                      use_matched if variant == "reuse" else 0,
                                      rbucket, variant, occ)
                except _PagePressure:
                    # the full escalation ladder (free list -> tree
                    # eviction -> preemption) ran dry: roll the admission
                    # back and STOP admitting this step.  Progress is
                    # still guaranteed — resident slots keep decoding,
                    # retiring slots free pages, and the pool floor
                    # (nb_max + 2) means a lone request always fits.
                    self._unadmit(i, req)
                    return
                except SimulatedFault as e:
                    # a device fault during placement (injected page
                    # allocation failure): placement already rolled its
                    # references back all-or-nothing, so the pool is
                    # clean — charge the request's fault budget and
                    # either retry it later or fail it terminally
                    self.stats.device_faults += 1
                    if self._charge_fault(req):
                        self._fail_request(req, "device_fault", str(e),
                                           slot=i)
                        continue
                    self._unadmit(i, req)
                    return
                continue
            # -- contiguous: atomic admission (the monolithic baseline) --
            jits_before = self._prefill_jit_cache_size()
            t0 = time.perf_counter()
            if use_matched and variant == "reuse":
                first, k_all, v_all, base = self._prefill_from_prefix(
                    i, prompt, req.cache_handle)
                self.stats.prefix_tokens_saved += use_matched
            else:
                first, k_all, v_all, base = self._prefill_full(i, prompt)
            # fence EVERYTHING the admission dispatched — otherwise that
            # device time both undercounts this admission's sample and
            # leaks into the NEXT decode step's VPE sample
            jax.block_until_ready(self.cache)
            if self.pages is not None:
                jax.block_until_ready(self.page_pool)
            dt = time.perf_counter() - t0
            self.stats.prefill_s += dt
            tainted = self._prefill_jit_cache_size() != jits_before
            if rbucket is not None and not tainted:
                # feed the measured TTFT contribution back: the controller
                # blind-trials "recompute" and keeps whichever is faster
                # for this matched-length bucket.  Samples that paid a
                # fresh jit compile are dropped: a plen bucket spans many
                # pad shapes, and one recorded multi-second compile would
                # permanently flip the bucket.
                self.vpe.profiler.record("prefix_reuse", variant, rbucket, dt)
                self.vpe.controller.on_sample("prefix_reuse", rbucket, variant)
            slot.admit_wall = dt
            slot.tainted = tainted
            self._enter_decode(i, first)
            # population is off the TTFT critical path: the first token is
            # already out; new full blocks enter the tree now
            self._cache_extend(req, k_all, v_all, base, slot)
            self._retire_if_done(i)

    def _unadmit(self, i: int, req: Request) -> None:
        """Undo a half-done admission whose placement rolled back: free
        the slot, unpin the prefix handle, requeue the request at the
        head of its own priority class (:meth:`_requeue` — NOT the queue
        head, which would park a rolled-back batch request ahead of
        waiting interactive traffic).  Its first-admission
        queue-wait/TTFT accounting is already recorded and is not
        repeated."""
        slot = self.slots[i]
        slot.req = None
        slot.prefilling = False
        slot.admit_bucket = None
        slot.reuse_bucket = None
        slot.chunk_bucket = None
        if req.cache_handle is not None:
            self.prefix_cache.release(req.cache_handle)
            req.cache_handle = None
        req.status = "queued"
        self._requeue(req)
        self._masks_dirty = True

    def _select_layout(self, matched: int) -> Tuple[str, Optional[Tuple]]:
        """Resolve this admission's KV layout (and its VPE bucket)."""
        if self.kv_layout != "auto":
            return self.kv_layout, None
        bucket = kv_layout_bucket(matched, self.num_active, self.num_slots,
                                  levels=self.occupancy_levels)
        if self.vpe is None:
            return "contiguous", bucket
        return self.vpe.controller.select("kv_layout", bucket), bucket

    def _enter_decode(self, i: int, first: int) -> None:
        """Transition a slot to the decoding state: emit the first
        generated token (TTFT) and reset the per-step attribution."""
        slot = self.slots[i]
        req = slot.req
        if not req.ttft_recorded:
            # once per request: a preempted-and-resumed request's first
            # token already shipped in its first residency
            req.ttft_s = time.perf_counter() - req.submit_t
            self.stats.ttft_s.append(req.ttft_s)
            req.ttft_recorded = True
        # cache coverage BEFORE this emission: prompt + prior output
        eff_len = len(req.prompt) + len(req.out)
        req.out.append(first)
        if self.proposer is not None:
            # seed the slot's draft context from prompt + anything a
            # previous residency already emitted (preemption resume),
            # then feed the fresh first token through the commit path —
            # prompts are where cross-request repetition lives, so the
            # table warms before the first decode step runs
            self.proposer.observe_prompt(
                i, [int(t) for t in req.prompt] + req.out[:-1])
            self.proposer.observe(i, [first])
        self.stats.tokens_out += 1
        self.stats.prefill_tokens += 1
        slot.prefilling = False
        slot.tok = first
        slot.pos = eff_len
        slot.steps_resident = 0
        slot.clean_step_shares = []
        self._masks_dirty = True     # live/tok/eos device arrays stale

    def _select_chunk(self, S: int, occ: int):
        """Resolve this admission's chunk size (tokens; 0 = whole) and,
        in auto mode, its ``prefill_chunk`` bucket + variant name."""
        if self.prefill_chunk == "auto" and self.vpe is not None:
            bucket = prefill_chunk_bucket(S, occ, self.num_slots,
                                          levels=self.occupancy_levels)
            if self.slo_weight > 0:
                bucket = bucket + self._slo_bucket()
            # shard count is a dispatch dimension: chunk-size tradeoffs
            # shift with the per-call collective cost of an mp-sharded
            # step (empty tail on a (1,1) mesh — keys stay unchanged)
            bucket = bucket + self._shard_tail
            name = self.vpe.controller.select("prefill_chunk", bucket)
            return (0 if name == "whole" else int(name)), bucket, name
        if self.prefill_chunk in (0, "whole", "auto"):
            return 0, None, None
        return int(self.prefill_chunk), None, None

    def _resolve_impl(self, name: str) -> str:
        """Fallback ladder for decode variants: a kernel-backed name
        resolves to "grouped" (whose paged read is the gather path)
        whenever this engine fails the pallas capability gate — a pinned
        or foreign-engine-selected "pallas" degrades, never crashes.
        Since PR 10 the same ladder serves as a RUNTIME demotion: a
        device fault attributed to the kernel path pins the resolution
        for a probation window (docs/fault_tolerance.md)."""
        if name in kvcache.PAGED_KERNEL_IMPLS \
                and (not self._pallas_ok
                     or self._rung_demoted("decode_pallas")):
            return "grouped"
        return name

    def _resolve_kernel(self, name: str) -> str:
        """Same ladder (capability gate + runtime demotion) for the
        prefill chunk-attention backend."""
        if name in kvcache.PAGED_KERNEL_IMPLS \
                and (not self._pallas_ok
                     or self._rung_demoted("prefill_pallas")):
            return "gather"
        return name

    def _select_prefill_kernel(self, S: int, occ: int):
        """Resolve the chunk-attention backend for this admission and,
        in auto mode, its ``prefill_kernel`` bucket + variant name.
        Keyed by the SAME prompt-length × occupancy construction as
        :meth:`_select_chunk` (the ISSUE's sibling-axis contract), so
        the controller learns gather-vs-kernel per (chunk bucket ×
        shard) configuration."""
        if self.prefill_kernel != "auto":
            return self._resolve_kernel(self.prefill_kernel), None, None
        if self.vpe is None or not self._pallas_ok:
            return "gather", None, None
        bucket = prefill_chunk_bucket(S, occ, self.num_slots,
                                      levels=self.occupancy_levels)
        if self.slo_weight > 0:
            bucket = bucket + self._slo_bucket()
        bucket = bucket + self._shard_tail
        name = self.vpe.controller.select("prefill_kernel", bucket)
        return self._resolve_kernel(name), bucket, name

    def _prefill_chunk_fn(self, kernel: str) -> Callable:
        """The chunked-prefill jit for one chunk-attention backend
        (built lazily; all live backends are summed by
        :meth:`_prefill_jit_cache_size` for taint detection)."""
        fn = self._prefill_chunks.get(kernel)
        if fn is None:
            cfg = self.cfg
            fn = jax.jit(
                lambda p, pool, bt, t, b, n, _k=kernel:
                    model_lib.prefill_chunk_paged(
                        cfg, p, pool, bt, t, b, n, kernel=_k),
                donate_argnums=1)
            self._prefill_chunks[kernel] = fn
        return fn

    def _place_paged(self, i: int, req: Request, reuse_matched: int,
                     rbucket, variant: str, occ: int) -> None:
        """Paged admission = placement only, O(1) in matched AND prompt
        length: alias the matched pages (zero-copy, one pool reference
        each), clone a partially matched tail block copy-on-write,
        allocate pages covering the rest of the prompt, install the
        device block-table row.  No prompt compute happens here — the
        slot enters the prefilling state and :meth:`_run_prefill_chunks`
        feeds it through :func:`~repro.models.transformer.
        prefill_chunk_paged` between decode steps.  The timed span
        (``kv_place_s``) is the placement cost the paged layout exists
        to keep flat.

        Placement is ALL-OR-NOTHING: every reference taken (aliased
        prefix pages, the COW clone, suffix allocations) is tracked, and
        if the allocation escalation runs dry mid-placement every one of
        them is returned before :class:`_PagePressure` propagates to
        :meth:`_admit` — a failed placement leaks zero pages and leaves
        the pool audit-clean (:meth:`check_kv`)."""
        slot = self.slots[i]
        prompt = req.effective_prompt()
        S = len(prompt)
        handle = req.cache_handle
        self._release_slot_pages(i)
        jits_before = self._prefill_jit_cache_size()
        t0 = time.perf_counter()
        rank = PRIORITY_RANK[req.priority]
        aliased: List[int] = []       # tree refs taken (rollback: unref)
        acquired: List[int] = []      # fresh allocations (rollback: unref)
        try:
            if reuse_matched:
                P = handle.matched_len
                for pid in handle.block_ids:      # full blocks: zero-copy
                    self.pages.ref(pid)
                    aliased.append(pid)
                cow = None
                if handle.partial_len:
                    # the first chunk's write lands mid-block in the
                    # partially matched page — clone it so the cached
                    # original (and anyone else aliasing it) cannot see
                    # this slot's writes
                    cow = self._alloc_page(exclude=i, rank=rank)
                    acquired.append(cow)
                    self.page_pool = self._copy_page(
                        self.page_pool, jnp.int32(handle.partial_block_id),
                        jnp.int32(cow))
                    self.stats.cow_copies += 1
                suffix_ids, _starts = self._suffix_page_ids(
                    P, S, cow, exclude=i, rank=rank, acquired=acquired)
                pages = aliased + suffix_ids
            else:
                P = 0
                pages, _starts = self._suffix_page_ids(
                    0, S, None, exclude=i, rank=rank, acquired=acquired)
        except (_PagePressure, SimulatedFault):
            # same all-or-nothing rollback for pressure AND injected
            # allocation faults: a failed placement leaks zero pages
            for pid in aliased + acquired:
                self.pages.unref(pid)
            self.stats.placement_rollbacks += 1
            raise
        if P:
            self.stats.prefix_tokens_saved += P
        # device row now (length stays 0 until the prefill completes —
        # the slot is excluded from decode via the live mask meanwhile)
        self._page_row(i, pages, 0)
        if req.swap is not None:
            P = self._swap_in_slot(i)
        jax.block_until_ready(self.cache)
        jax.block_until_ready(self.page_pool)     # the COW copy, if any
        dt = time.perf_counter() - t0
        self.stats.kv_place_s.append(dt)
        self.stats.prefill_s += dt
        self.stats.paged_admits += 1
        slot.prefilling = True
        slot.fill_pos = P
        slot.place_wall = dt
        slot.tainted = self._prefill_jit_cache_size() != jits_before
        slot.reuse_bucket = rbucket
        slot.reuse_variant = variant
        slot.chunk_walls = []
        slot.chunk_costs = []
        slot.chunk, slot.chunk_bucket, slot.chunk_variant = \
            self._select_chunk(S, occ)
        slot.kernel, slot.kernel_bucket, slot.kernel_variant = \
            self._select_prefill_kernel(S, occ)

    def _effective_chunk_budget(self) -> int:
        """Chunks allowed this engine step.  An explicit
        ``chunks_per_step`` pins the budget; the adaptive default
        (``None``) spends 1 when decoding slots are resident (their
        service interruption is what the budget bounds) and, when no
        slot is decoding, one chunk per prefilling slot — there is
        nothing to stall, so batching chunks only shortens TTFT
        (ROADMAP "chunk-budget adaptivity")."""
        if self.chunks_per_step is not None:
            return self.chunks_per_step
        if self.num_decoding > 0:
            return 1
        return max(1, sum(1 for s in self.slots if s.prefilling))

    def _run_prefill_chunks(self) -> bool:
        """Run at most the step's chunk budget of prefill chunks,
        round-robin over the slots currently in the prefilling state —
        the budget bounds decode service interruption per engine step."""
        ran = False
        budget = self._effective_chunk_budget()
        for _ in range(budget):
            order = [(self._chunk_rr + k) % self.num_slots
                     for k in range(self.num_slots)]
            i = next((j for j in order if self.slots[j].prefilling), None)
            if i is None:
                break
            self._chunk_rr = (i + 1) % self.num_slots
            self._run_one_chunk(i)
            ran = True
        if ran:
            self.stats.chunk_budget_hist[budget] = \
                self.stats.chunk_budget_hist.get(budget, 0) + 1
        return ran

    def _run_one_chunk(self, i: int) -> None:
        """One chunk of slot ``i``'s prompt: read every prior position in
        place through the block table, scatter the chunk's own K/V into
        its pages.  The final chunk yields the first generated token."""
        slot = self.slots[i]
        req = slot.req
        fault = self._take_fault("prefill")
        if fault is not None and fault.kind == "device":
            # the chunk call raised before dispatch: nothing was
            # computed, fill_pos is untouched.  Blame the kernel backend
            # when one ran (demotion re-resolves this slot's NEXT chunk
            # to gather in place); otherwise charge the request.
            self.stats.device_faults += 1
            if slot.kernel in kvcache.PAGED_KERNEL_IMPLS:
                self._demote("prefill_pallas")
            elif self._charge_fault(req):
                self._fail_request(
                    req, "device_fault",
                    "injected device fault in prefill chunk", slot=i)
            else:
                self._preempt_slot(i)
            return
        prompt = req.effective_prompt()
        S = len(prompt)
        base = slot.fill_pos
        clen = (S - base) if not slot.chunk else min(slot.chunk, S - base)
        pad = min(pad_to_bucket(clen, minimum=self.min_prompt_pad),
                  self.max_len)
        toks = np.zeros((1, pad), np.int32)
        toks[0, :clen] = prompt[base:base + clen]
        row = self._bt_row(slot.pages)
        prefill_fn = self._prefill_chunk_fn(slot.kernel)
        jits_before = self._prefill_jit_cache_size()
        t0 = time.perf_counter()
        self.page_pool, logits = prefill_fn(
            self.params, self.page_pool, jnp.asarray(row), jnp.asarray(toks),
            jnp.int32(base), jnp.int32(clen))
        # fence: an async chunk would leak its device time into the next
        # decode step's VPE sample (and undercount this admission's)
        jax.block_until_ready((self.page_pool, logits))
        dt = time.perf_counter() - t0
        slot.chunk_walls.append(dt)
        # the scheduler objective's second term: charge the chunk for the
        # class-weighted queue wait it imposed while it ran
        slot.chunk_costs.append(
            dt * (1.0 + self.slo_weight * self._queue_pressure()))
        if self._prefill_jit_cache_size() != jits_before:
            slot.tainted = True
        self.stats.prefill_s += dt
        self.stats.prefill_chunks += 1
        if fault is not None:
            if fault.kind == "nan":
                # poisoned chunk logits: the K/V this chunk scattered is
                # untrusted too — quarantine the slot (recompute-resume)
                # before fill_pos could count the poisoned positions
                self._numeric_fault(
                    i, "injected NaN logits in prefill chunk")
                return
            # stall: the value arrived late — commit it, count the trip
            if self.watchdog is not None:
                self.watchdog.trips += 1
            self.stats.watchdog_trips += 1
            slot.tainted = True
        slot.fill_pos = base + clen
        if slot.fill_pos >= S:
            self._finish_prefill(i, logits)

    def _finish_prefill(self, i: int, logits) -> None:
        """Last chunk done: first token out, device length installed,
        measured samples fed to the ``prefix_reuse`` and
        ``prefill_chunk`` controllers (the admission's TTFT contribution
        is placement + summed chunk walls; compile-tainted admissions
        are dropped), and the prompt's fresh full blocks adopted into
        the prefix tree zero-copy."""
        slot = self.slots[i]
        req = slot.req
        # resumed residencies prefilled prompt + prior output
        S = len(req.prompt) + len(req.out)
        first = int(np.asarray(jnp.argmax(logits[0])))
        self.cache = self._set_len(self.cache, jnp.int32(i), jnp.int32(S))
        slot.admit_wall = slot.place_wall + sum(slot.chunk_walls)
        if self.vpe is not None and not slot.tainted:
            if slot.reuse_bucket is not None:
                self.vpe.profiler.record("prefix_reuse", slot.reuse_variant,
                                         slot.reuse_bucket, slot.admit_wall)
                self.vpe.controller.on_sample("prefix_reuse",
                                              slot.reuse_bucket,
                                              slot.reuse_variant)
            if slot.chunk_bucket is not None:
                # the chunk-size decision only moves the chunk compute,
                # not the (size-independent) placement — feed exactly
                # that, SLO-charged: with slo_weight > 0 each chunk's
                # wall is scaled by the queue pressure it ran under, so
                # the controller prefers small chunks when urgent work
                # waits (identical to raw walls at slo_weight == 0)
                self.vpe.profiler.record("prefill_chunk", slot.chunk_variant,
                                         slot.chunk_bucket,
                                         sum(slot.chunk_costs))
                self.vpe.controller.on_sample("prefill_chunk",
                                              slot.chunk_bucket,
                                              slot.chunk_variant)
            if slot.kernel_bucket is not None:
                # the kernel decision moves the same chunk compute the
                # chunk-size decision does — feed the identical
                # SLO-charged clean chunk walls under the identical
                # taint discipline (sibling axis, same bucket family)
                self.vpe.profiler.record("prefill_kernel",
                                         slot.kernel_variant,
                                         slot.kernel_bucket,
                                         sum(slot.chunk_costs))
                self.vpe.controller.on_sample("prefill_kernel",
                                              slot.kernel_bucket,
                                              slot.kernel_variant)
        slot.reuse_bucket = None
        slot.chunk_bucket = None
        slot.kernel_bucket = None
        self._enter_decode(i, first)
        self._cache_extend(req, None, None, 0, slot)
        self._retire_if_done(i)

    def _prefill_jit_cache_size(self) -> int:
        """Total compiled-specialization count of the admission-path jits
        (a growth across a timed section means that sample paid a trace+
        compile and must not feed the ``prefix_reuse``/``kv_layout``
        controllers)."""
        fns = [self._prefill, self._insert]
        if self.pages is not None:
            fns += [self._gather_pages, self._write_pages, self._copy_page,
                    self._admit_paged, self._set_bt, self._set_bt_many,
                    self._set_len, *self._prefill_chunks.values(),
                    self._swap_gather, self._swap_scatter]
        if self.prefix_cache is not None:
            fns += [self._insert_at, self._prefill_suffix]
            if self.pages is None:
                fns += [self._gather, self._write_block]
        return sum(f._cache_size() for f in fns)

    # -- contiguous-layout admission paths ----------------------------------
    def _prefill_full(self, i: int, prompt: np.ndarray):
        """Cold path: run the whole prompt and insert at slot position 0."""
        S = len(prompt)
        pad = min(pad_to_bucket(S, minimum=self.min_prompt_pad), self.max_len)
        toks = np.zeros((1, pad), np.int32)
        toks[0, :S] = prompt
        k, v, logits = self._prefill(self.params, jnp.asarray(toks), jnp.int32(S))
        # fence the prefill BEFORE the placement timer: the insert fence
        # below transitively waits on its inputs, and an async prefill
        # inside the span would record the O(S^2) compute as "placement"
        jax.block_until_ready(k)
        t0 = time.perf_counter()
        self.cache = self._insert(self.cache, k, v, jnp.int32(i), jnp.int32(S))
        jax.block_until_ready(self.cache)
        self.stats.kv_place_s.append(time.perf_counter() - t0)
        first = int(np.asarray(jnp.argmax(logits[0])))
        return first, k, v, 0

    def _prefill_from_prefix(self, i: int, prompt: np.ndarray, handle):
        """Contiguous warm path: gather the matched pages, COPY them into
        the slot's region, prefill only the suffix.

        Page ids are padded to a power-of-two count (bounded jit shapes);
        padded columns sit past ``prefix_len`` and are masked inside the
        suffix prefill.  Slot writes go prefix-then-suffix so any padded
        prefix garbage in ``[prefix_len, P_pad)`` is overwritten or
        masked by ``length``.  The timed KV-placement span (gather +
        prefix copy-in) is the O(matched-length) cost the paged layout
        removes.
        """
        S = len(prompt)
        P = self.block_size * len(handle.nodes)   # full blocks only
        bs = self.block_size
        nb = P // bs
        nb_pad = min(pad_to_bucket(nb, minimum=1), self.max_len // bs)
        # pad by repeating a pinned id (gather contract: padded ids
        # must be valid pages; matched > 0 guarantees at least one)
        ids = np.asarray(
            handle.block_ids + [handle.block_ids[0]] * (nb_pad - nb), np.int32)
        t0 = time.perf_counter()
        pk, pv = self._gather_prefix(ids)
        cache = self._insert_at(self.cache, pk, pv, jnp.int32(i), jnp.int32(0),
                                jnp.int32(S))
        jax.block_until_ready(cache)
        self.stats.kv_place_s.append(time.perf_counter() - t0)
        sl = S - P
        pad_s = min(pad_to_bucket(sl, minimum=self.min_prompt_pad),
                    self.max_len - P)
        toks = np.zeros((1, pad_s), np.int32)
        toks[0, :sl] = prompt[P:]
        k, v, logits = self._prefill_suffix(
            self.params, jnp.asarray(toks), pk, pv, jnp.int32(P), jnp.int32(sl))
        self.cache = self._insert_at(cache, k, v, jnp.int32(i), jnp.int32(P),
                                     jnp.int32(S))
        first = int(np.asarray(jnp.argmax(logits[0])))
        return first, k, v, P

    def _gather_prefix(self, ids: np.ndarray):
        """Gather cached prefix pages from whichever pool this engine's
        layouts share (values are identical either way — both pools are
        written from the same prefill outputs)."""
        if self.pages is not None:
            return self._gather_pages(self.page_pool, jnp.asarray(ids))
        return self._gather(self.block_pool, jnp.asarray(ids))

    # -- paged-layout admission paths ---------------------------------------
    def _bt_row(self, pages: List[int]) -> np.ndarray:
        """A slot's full (nb_max,) block-table row, trash-padded past its
        allocated pages — the one padding convention shared by the device
        row install and the chunk jit's host-side argument."""
        row = np.full((self.nb_max,), self.pages.trash_id, np.int32)
        row[:len(pages)] = pages
        return row

    def _page_row(self, i: int, pages: List[int], true_len: int) -> None:
        """Install a slot's block table row + length on device (tiny
        host->device transfer: nb_max ids, the O(1)-in-matched-length
        'copy' of the paged layout)."""
        self.cache = self._admit_paged(self.cache,
                                       jnp.asarray(self._bt_row(pages)),
                                       jnp.int32(i), jnp.int32(true_len))
        self.slots[i].pages = list(pages)

    def _suffix_page_ids(self, base: int, S: int, cow_page: Optional[int],
                         *, exclude: Optional[int] = None,
                         rank: Optional[int] = None,
                         acquired: Optional[List[int]] = None
                         ) -> Tuple[List[int], List[int]]:
        """Allocate pages covering prompt positions ``[base, S)``.

        Returns (write_ids, write_starts) for :func:`kvcache.write_pages`
        — ``cow_page`` (the copy-on-write clone of a partially matched
        block) is the first write target when ``base`` is mid-block.
        ``exclude``/``rank`` thread through to :meth:`_alloc_page`'s
        preemption escalation; every page allocated HERE is appended to
        ``acquired`` as it is taken, so a mid-run :class:`_PagePressure`
        leaves the caller an exact rollback list.
        """
        bs = self.block_size
        ids, starts = [], []
        b = base // bs
        while b * bs < S:
            if cow_page is not None and b == base // bs and base % bs:
                pid = cow_page
            else:
                pid = self._alloc_page(exclude=exclude, rank=rank)
                if acquired is not None:
                    acquired.append(pid)
            ids.append(pid)
            starts.append(b * bs)
            b += 1
        return ids, starts

    def _write_suffix_pages(self, k_all, v_all, ids: List[int],
                            starts: List[int], base: int, S: int) -> None:
        """One masked scatter for every page the prefill produced (ids
        padded to a power-of-two count with the trash page — bounded jit
        specializations, garbage writes land on the trash row)."""
        n_pad = pad_to_bucket(len(ids), minimum=1)
        trash = self.pages.trash_id
        ids_pad = np.asarray(ids + [trash] * (n_pad - len(ids)), np.int32)
        # padded starts sit a full block before ``base`` so their source
        # window is entirely invalid (write_pages keeps old content)
        starts_pad = np.asarray(
            starts + [base - self.block_size] * (n_pad - len(starts)), np.int32)
        self.page_pool = self._write_pages(
            self.page_pool, k_all, v_all, jnp.asarray(ids_pad),
            jnp.asarray(starts_pad), jnp.int32(base), jnp.int32(S - base))

    # NOTE: the PR 3 atomic paged prefill paths (_prefill_full_paged /
    # _prefill_from_prefix_paged — the latter materialized an O(matched)
    # transient gather of the prefix for the suffix's attention) are
    # gone: every paged admission now goes through _place_paged +
    # _run_prefill_chunks, which read prior pages in place.  The
    # contiguous copy-in paths above stay as the monolithic baseline
    # and parity anchor.

    def _release_slot_pages(self, i: int) -> None:
        """Drop the slot's references from a previous residency (pages the
        tree adopted survive through the tree's own reference)."""
        for pid in self.slots[i].pages:
            self.pages.unref(pid)
        self.slots[i].pages = []

    def _cache_extend(self, req: Request, k_all, v_all, base: int,
                      slot: _Slot) -> None:
        """Insert the prompt's not-yet-cached full blocks into the tree.

        Paged slots hand their OWN pages to the tree (zero-copy
        adoption: one extra pool reference per block, no device
        traffic).  Contiguous slots copy the freshly computed K/V into
        tree-allocated pages, exactly as in PR 2 — paid only when a
        prefix is seen for the FIRST time (the paper's warm-up phase).
        """
        if self.prefix_cache is None:
            return
        if slot.layout == "paged":
            bs = self.block_size
            page_of_block = {j: pid for j, pid in enumerate(slot.pages)}
            # the copy-on-write clone of a partial block may be adopted
            # too IF the prompt filled it completely (its content is then
            # exactly the block's tokens' K/V); write_pages already left
            # positions >= S untouched, so a half-filled tail block is
            # excluded by extend's full-blocks-only walk
            self.prefix_cache.extend_adopt(req.cache_handle, req.prompt,
                                           page_of_block)
            return
        fresh = self.prefix_cache.extend(req.cache_handle, req.prompt)
        if not fresh:
            return
        if self.pages is not None:
            # auto mode, contiguous admission: tree pages live in the
            # unified pool — fill them with one masked scatter
            ids = [bid for bid, _ in fresh]
            starts = [st for _, st in fresh]
            self._write_suffix_pages(k_all, v_all, ids, starts, base,
                                     int(len(req.prompt)))
            jax.block_until_ready(self.page_pool)
            return
        # one dispatch per fresh block: acceptable because it is paid only
        # when a prefix is seen for the FIRST time (the paper's warm-up
        # phase); a batched scatter would trade it for a jit
        # specialization per distinct block count
        for bid, start in fresh:
            self.block_pool = self._write_block(
                self.block_pool, k_all, v_all, jnp.int32(bid),
                jnp.int32(start - base))
        # fence the page writes: otherwise their device time leaks
        # into the next decode step's timed VPE sample
        jax.block_until_ready(self.block_pool)

    def _retire_if_done(self, i: int) -> None:
        slot = self.slots[i]
        req = slot.req
        if req is None:
            return
        hit_eos = req.eos_id is not None and req.out and req.out[-1] == req.eos_id
        if len(req.out) >= req.max_new_tokens or hit_eos:
            req.done = True
            req.status = "done"
            req.done_step = self.stats.decode_steps
            req.done_t = time.perf_counter()
            if slot.layout == "paged":
                # drop the block table's pool references NOW: anything the
                # tree adopted (or this slot aliased) stays alive through
                # the tree's own reference; private pages free immediately
                self._release_slot_pages(i)
            if req.cache_handle is not None:
                # unpin: the pages this request matched/inserted become
                # evictable again (the paged slot no longer references
                # them either — see above)
                self.prefix_cache.release(req.cache_handle)
                req.cache_handle = None
            if slot.admit_bucket is not None and self.vpe is not None \
                    and not slot.tainted:
                # the kv_layout sample: admission wall + this request's
                # decode component, rebuilt from per-step CLEAN timings
                # (steps whose fenced wall included a decode-jit compile
                # are excluded and their cost extrapolated from the clean
                # mean) — a residency whose every step paid a compile has
                # no clean signal and is dropped entirely
                comp, ok = 0.0, True
                if slot.steps_resident:
                    if slot.clean_step_shares:
                        comp = (sum(slot.clean_step_shares)
                                / len(slot.clean_step_shares)
                                * slot.steps_resident)
                    else:
                        ok = False
                if ok:
                    self.vpe.profiler.record(
                        "kv_layout", slot.layout, slot.admit_bucket,
                        slot.admit_wall + comp)
                    self.vpe.controller.on_sample(
                        "kv_layout", slot.admit_bucket, slot.layout)
            slot.admit_bucket = None
            self.completed.append(req)
            slot.req = None   # freed mid-decode; refilled next admission
            if self.proposer is not None:
                self.proposer.forget_slot(i)
            self._masks_dirty = True

    # -- decode -------------------------------------------------------------
    def _grow_block_tables(self, span: int = 1,
                           remaining: Optional[Dict[int, int]] = None) -> None:
        """Before a decode call: reserve, for every live paged slot, the
        pages covering its next ``span`` write positions — clipped to the
        slot's ``remaining`` token budget when given — and install every
        splice in ONE batched scatter.  ``span=1`` is the classic
        single-step growth (a page exactly when the next token starts a
        fresh block); a fused horizon pre-reserves its whole write range
        ``[pos, pos + min(span, remaining))`` because mid-horizon there
        is no host to allocate a page.  (The tail page is guaranteed
        private by admission-time copy-on-write, so decode appends never
        need a COW check.)

        Under page pressure the allocation escalates (eviction, then
        preemption of equal-or-lower-priority prefills and strictly
        lower-priority decoders); if even that runs dry the growing slot
        preempts ITSELF — its request resumes exactly later via
        :meth:`Request.effective_prompt` — rather than crashing the
        step.  A victim preempted mid-loop may be a slot that grew
        earlier in the same loop, so only splices whose slot still owns
        the page are installed."""
        splices: List[Tuple[int, int, int]] = []
        for i, slot in enumerate(self.slots):
            if slot.free or slot.prefilling or slot.layout != "paged":
                continue
            upto = slot.pos + (span if remaining is None
                               else min(span, remaining[i]))
            last_col = (upto - 1) // self.block_size
            assert last_col < self.nb_max, (last_col, self.nb_max)
            rank = PRIORITY_RANK[slot.req.priority]
            try:
                for col in range(len(slot.pages), last_col + 1):
                    pid = self._alloc_page(exclude=i, rank=rank,
                                           decode_growth=True)
                    slot.pages.append(pid)
                    splices.append((i, col, pid))
            except _PagePressure:
                # nothing left to take anywhere: the grower yields its
                # own residency (pages already appended this loop are
                # released with the rest of the slot's pages)
                self._preempt_slot(i)
            except SimulatedFault as e:
                # injected allocation fault mid-growth: the slot's KV is
                # clean (nothing was computed), so the request either
                # retries via preemption-resume or — budget spent —
                # fails terminally; its pages release either way
                self.stats.device_faults += 1
                if self._charge_fault(slot.req):
                    self._fail_request(slot.req, "device_fault", str(e),
                                       slot=i)
                else:
                    self._preempt_slot(i)
        splices = [(i, col, pid) for (i, col, pid) in splices
                   if self.slots[i].req is not None
                   and col < len(self.slots[i].pages)
                   and self.slots[i].pages[col] == pid]
        if not splices:
            return
        if len(splices) == 1:
            (i, col, pid), = splices
            self.cache = self._set_bt(self.cache, jnp.int32(i),
                                      jnp.int32(col), jnp.int32(pid))
            return
        # pad to a power-of-two splice count (bounded jit shapes) by
        # repeating the last real splice — an identical duplicate write
        n_pad = pad_to_bucket(len(splices), minimum=4)
        splices = splices + [splices[-1]] * (n_pad - len(splices))
        s, c, p = (np.asarray(x, np.int32) for x in zip(*splices))
        self.cache = self._set_bt_many(self.cache, jnp.asarray(s),
                                       jnp.asarray(c), jnp.asarray(p))

    def _decode_fn(self, bucket) -> Callable:
        if self.decode_impl != "auto":
            # pinned backend: no per-bucket selection (the axis is a
            # system op), and samples are recorded under the RESOLVED
            # name so a shared VPE sees what actually ran
            vname = self._resolve_impl(self.decode_impl)
        elif self.vpe is not None:
            # per-call selection (returns in-flight trials too) — the
            # eager analogue of the paper's patched function pointer.
            # Bookkeeping keeps the controller's selected name (so its
            # trial accounting converges); the jit below is keyed by the
            # RESOLVED name — on this engine a gated-out kernel variant
            # IS the grouped step, so the walls recorded for it are
            # truthful either way.
            vname = self.vpe.controller.select(self._axis, bucket)
        else:
            vname = self._default_variant
        self._last_variant = vname
        vname = self._resolve_impl(vname)
        fn = self._decode_fns.get(vname)
        if fn is None:
            if self._decode_fns:
                # an actual re-trace: a not-yet-compiled variant is baked
                # into the step (flips between already-compiled variants
                # are pointer swaps served from the jit cache, not rejits)
                self.stats.rejits += 1
            cfg = self.cfg
            # tokens arrive as the persistent (slots,) device array (the
            # previous step's own output — no host rebuild or re-upload
            # on steady decode steps); reshape to (slots, 1) in-graph
            if self.kv_layout == "paged":
                def _step(p, pool, c, t, live, v=vname):
                    pool, c, logits = model_lib.decode_step_paged(
                        cfg, p, pool, c, t[:, None], live, decode_impl=v)
                    return pool, c, jnp.argmax(
                        logits[:, -1, :], axis=-1).astype(jnp.int32)
                fn = jax.jit(_step, donate_argnums=(1, 2))
            elif self.kv_layout == "auto":
                def _step(p, c, pool, t, up, live, v=vname):
                    c, pool, logits = model_lib.decode_step_mixed(
                        cfg, p, c, pool, t[:, None], up, live, decode_impl=v)
                    return c, pool, jnp.argmax(
                        logits[:, -1, :], axis=-1).astype(jnp.int32)
                fn = jax.jit(_step, donate_argnums=(1, 2))
            else:
                def _step(p, c, t, v=vname):
                    c, logits = model_lib.decode_step_slots(
                        cfg, p, c, t[:, None], decode_impl=v)
                    # greedy argmax on device: only (slots,) ints cross host
                    return c, jnp.argmax(
                        logits[:, -1, :], axis=-1).astype(jnp.int32)
                fn = jax.jit(_step)
            self._decode_fns[vname] = fn
        return fn

    def _fused_fn(self, bucket, horizon: int) -> Callable:
        """The fused-horizon analogue of :meth:`_decode_fn`: one jitted
        H-step on-device loop per (decode-attention variant, H)."""
        if self.decode_impl != "auto":
            vname = self._resolve_impl(self.decode_impl)
        elif self.vpe is not None:
            vname = self.vpe.controller.select(self._axis, bucket)
        else:
            vname = self._default_variant
        self._last_variant = vname
        vname = self._resolve_impl(vname)
        key = (vname, horizon)
        fn = self._fused_fns.get(key)
        if fn is None:
            if self._fused_fns or self._decode_fns:
                self.stats.rejits += 1
            cfg = self.cfg
            if self.kv_layout == "paged":
                def _steps(p, pool, c, t, live, eos, bud,
                           v=vname, h=horizon):
                    return model_lib.decode_steps_paged(
                        cfg, p, pool, c, t[:, None], live, eos, bud, h,
                        decode_impl=v)
                fn = jax.jit(_steps, donate_argnums=(1, 2))
            elif self.kv_layout == "auto":
                def _steps(p, c, pool, t, up, live, eos, bud,
                           v=vname, h=horizon):
                    return model_lib.decode_steps_mixed(
                        cfg, p, c, pool, t[:, None], up, live, eos, bud, h,
                        decode_impl=v)
                fn = jax.jit(_steps, donate_argnums=(1, 2))
            else:
                def _steps(p, c, t, live, eos, bud, v=vname, h=horizon):
                    return model_lib.decode_steps_slots(
                        cfg, p, c, t[:, None], live, eos, bud, h,
                        decode_impl=v)
                fn = jax.jit(_steps, donate_argnums=(1,))
            self._fused_fns[key] = fn
        return fn

    def _select_horizon(self, n_active: int
                        ) -> Tuple[int, Optional[Tuple], Optional[str]]:
        """Resolve this step's decode horizon (and, in auto mode, its
        VPE bucket + variant name).  The bucket is keyed by the queue
        depth REMAINING after this step's admission phase — the requests
        a fused horizon would actually delay — × occupancy."""
        if self._rung_demoted("horizon"):
            # runtime demotion overrides even a pinned horizon: a
            # faulted fused span retries as single steps until the
            # probation window passes (docs/fault_tolerance.md)
            return 1, None, None
        if self.decode_horizon != "auto":
            return int(self.decode_horizon), None, None
        bucket = decode_horizon_bucket(len(self.queue), n_active,
                                       self.num_slots,
                                       levels=self.occupancy_levels)
        if self.slo_weight > 0:
            # SLO-aware mode: the horizon decision additionally depends
            # on WHO is waiting (an interactive waiter makes long fused
            # calls expensive under the two-term objective)
            bucket = bucket + self._slo_bucket()
        # per-mesh horizon policy: a sharded step amortizes BOTH host
        # overhead and collective latency, so the best H moves with the
        # shard count (empty tail on a (1,1) mesh)
        bucket = bucket + self._shard_tail
        if self.vpe is None:
            return 1, None, None
        name = self.vpe.controller.select("decode_horizon", bucket)
        return int(name), bucket, name

    def _refresh_device_masks(self) -> None:
        """Rebuild the persistent device-side decode inputs from the
        host slot mirrors — only after an admission/retire/prefill-
        completion event dirtied them.  Steady decode-bound steps skip
        this entirely: the input token array is the previous call's own
        on-device output and the masks are unchanged."""
        if not self._masks_dirty:
            return
        self._tok_dev = jnp.asarray(
            np.array([s.tok for s in self.slots], np.int32))
        self._live_dev = jnp.asarray(
            np.array([0 if (s.free or s.prefilling) else 1
                      for s in self.slots], np.int32))
        self._eos_dev = jnp.asarray(
            np.array([-1 if (s.req is None or s.req.eos_id is None)
                      else s.req.eos_id for s in self.slots], np.int32))
        if self.kv_layout == "auto":
            self._use_paged_dev = jnp.asarray(
                np.array([1 if s.layout == "paged" else 0
                          for s in self.slots], np.int32))
        self._masks_dirty = False

    def _bt_jit_cache_size(self) -> int:
        """Compiled-specialization count of the block-table splice jits.
        They trace lazily mid-serve (first splice, first crossing into a
        bigger pad bucket), inside the horizon axis's timed span — a
        growth across a step means that step's sample paid a compile and
        must be dropped, exactly like a decode-fn compile."""
        if self.pages is None:
            return 0
        return self._set_bt._cache_size() + self._set_bt_many._cache_size()

    def _rollback_reserved(self, i: int) -> None:
        """Return a slot's reserved-but-unwritten horizon pages to the
        refcounted pool (EOS froze the slot before it reached them).
        Pages covering ``[0, pos)`` stay; everything past the last
        written block goes back, so a drain audit sees zero leaks even
        when every horizon over-reserved."""
        slot = self.slots[i]
        keep = -(-slot.pos // self.block_size)      # ceil
        while len(slot.pages) > keep:
            self.pages.unref(slot.pages.pop())
            self.stats.reserved_pages_rolled_back += 1

    def _fused_decode(self, H: int, hbucket, hname,
                      remaining: Dict[int, int], t_h: float) -> None:
        """One fused H-step decode call: pre-reserve every page the
        horizon can write (ONE batched block-table scatter), run the
        on-device loop, fence once on the (slots, H) token block, replay
        it into per-request outputs, retire stopped slots and roll their
        unused reserved pages back."""
        fault = self._take_fault("fused")
        bt_jits = self._bt_jit_cache_size()
        if self.pages is not None:
            self._grow_block_tables(span=H, remaining=remaining)
            # growth may have preempted decoding slots (including a
            # grower preempting itself): prune them from this call and
            # refresh the masks the preemption dirtied
            remaining = {i: r for i, r in remaining.items()
                         if self.slots[i].req is not None
                         and not self.slots[i].prefilling}
            if not remaining:
                return
            self._refresh_device_masks()
        if fault is not None and fault.kind == "device":
            # the fused call raised before dispatch: donated buffers
            # unconsumed, horizon reservations rolled back, the horizon
            # rung demoted — next step retries as single steps
            self._span_device_fault(
                "horizon", list(remaining),
                "injected device fault in fused horizon call")
            return
        n_active = len(remaining)
        bucket = occupancy_bucket(n_active, self.num_slots,
                                  levels=self.occupancy_levels) \
            + self._shard_tail
        fn = self._fused_fn(bucket, H)
        jits = fn._cache_size()
        budget = np.zeros((self.num_slots,), np.int32)
        for i, rem in remaining.items():
            budget[i] = rem
        bud_dev = jnp.asarray(budget)
        t0 = time.perf_counter()
        if self.kv_layout == "paged":
            self.page_pool, cache, tok_block, valid, final_tok = fn(
                self.params, self.page_pool, self.cache, self._tok_dev,
                self._live_dev, self._eos_dev, bud_dev)
        elif self.kv_layout == "auto":
            cache, self.page_pool, tok_block, valid, final_tok = fn(
                self.params, self.cache, self.page_pool, self._tok_dev,
                self._use_paged_dev, self._live_dev, self._eos_dev, bud_dev)
        else:
            cache, tok_block, valid, final_tok = fn(
                self.params, self.cache, self._tok_dev, self._live_dev,
                self._eos_dev, bud_dev)
        toks, tripped = self._guarded_fence(tok_block, fault)
        emits = np.asarray(valid)
        dt = time.perf_counter() - t0
        self.cache = cache
        self._tok_dev = final_tok
        self.stats.decode_s += dt
        self.stats.decode_steps += H
        self.stats.horizon_calls += 1
        self.stats.horizon_hist[H] = self.stats.horizon_hist.get(H, 0) + 1
        if tripped:
            # the fence stalled: the tokens DID arrive (committed
            # below), but the fused span is what hung — demote the
            # horizon rung so the next calls stay host-interruptible
            self._demote("horizon")
        if fault is not None and fault.kind == "nan":
            # poisoned logits: out-of-vocab sentinel on the planned
            # slot's rows (or all) — the always-on validation below
            # quarantines exactly the slots a real NaN would hit
            toks = toks.copy()
            rows = ([fault.slot] if fault.slot is not None
                    and fault.slot in remaining else list(remaining))
            toks[rows, :] = -1
        step_tainted = fn._cache_size() != jits
        if self._bt_jit_cache_size() != bt_jits:
            step_tainted = True     # a splice jit compiled inside t_h
        if tripped:
            step_tainted = True     # a stalled wall must not feed axes
        if step_tainted:
            self.stats.tainted_steps += 1
        valid_total = int(emits.sum())
        self.stats.horizon_tokens += valid_total
        if self.vpe is not None and not tripped:
            # the decode-attention axis keeps per-STEP units (dt / H,
            # the same quantity its single-step samples measure)
            self.vpe.profiler.record(self._axis, self._last_variant, bucket,
                                     dt / H)
            self.vpe.controller.on_sample(self._axis, bucket,
                                          self._last_variant)
        share = dt / max(valid_total, 1)
        vocab = self.cfg.vocab_size
        quarantine: List[int] = []
        probe_off = probe_acc = 0
        self._probe_tick += 1
        probing = (self.spec_draft == "auto"
                   and self._probe_tick % self._probe_every == 0)
        for i in remaining:
            slot = self.slots[i]
            # a slot freezes at most once, so its valid tokens are a
            # contiguous prefix of the horizon
            e = int(emits[i].sum())
            new_toks = [int(t) for t in toks[i, :e]]
            if any(t < 0 or t >= vocab for t in new_toks):
                # always-on numeric validation: an out-of-range token
                # means this call's logits for the slot were garbage —
                # NOTHING from the call is committed for it (mid-span
                # poison taints the whole span) and the slot is
                # quarantined after the replay loop (preempting inside
                # it would mutate the slots being iterated)
                quarantine.append(i)
                continue
            slot.req.out.extend(new_toks)
            if self.proposer is not None:
                if probing and new_toks:
                    # counterfactual probe BEFORE observe() advances the
                    # slot's context (see _probe_accept)
                    probe_acc += self._probe_accept(i, new_toks)
                    probe_off += len(new_toks)
                self.proposer.observe(i, new_toks)
            slot.tok = new_toks[-1]
            slot.pos += e
            slot.steps_resident += e
            if not step_tainted:
                slot.clean_step_shares.extend([share] * e)
            self.stats.tokens_out += e
            if slot.layout == "paged":
                self._rollback_reserved(i)
            self._retire_if_done(i)
        for i in quarantine:
            self._numeric_fault(
                i, "out-of-range token from fused horizon call")
        self._update_accept_ema(probe_off, probe_acc)
        if fault is None and not tripped:
            self._tick_probation()
        if self.vpe is not None and hbucket is not None \
                and not step_tainted and valid_total:
            # per-TOKEN wall of the FULL span (reservation + call +
            # fence + replay — the overhead a horizon amortizes), with
            # compile-tainted calls dropped; frozen steps emit nothing,
            # so over-long horizons pay for themselves here.  The SLO
            # charge factor makes the same wall cost MORE when queued
            # (especially interactive) requests waited it out — the
            # two-term objective's second term.
            charge = 1.0 + self.slo_weight * self._queue_pressure()
            self.vpe.profiler.record("decode_horizon", hname, hbucket,
                                     (time.perf_counter() - t_h)
                                     / valid_total * charge)
            self.vpe.controller.on_sample("decode_horizon", hbucket, hname)
        if self.vpe is not None and self._spec_off_pending is not None \
                and not step_tainted and valid_total:
            # the spec axis selected "off" (or its span clamped out)
            # this step: the plain fused path IS the off variant, so
            # its per-committed-token wall feeds the spec axis in the
            # same units the verify path records — the off-vs-span
            # comparison the controller runs per bucket
            sb, sn = self._spec_off_pending
            charge = 1.0 + self.slo_weight * self._queue_pressure()
            self.vpe.profiler.record("spec_draft", sn, sb,
                                     (time.perf_counter() - t_h)
                                     / valid_total * charge)
            self.vpe.controller.on_sample("spec_draft", sb, sn)

    def _spec_fn(self, span: int) -> Callable:
        """The speculative-verify analogue of :meth:`_fused_fn`: one
        jitted S-position verify per span.  No decode-attention variant
        in the key — the verify read is the multi-query chunked-prefill
        generalization, not one of the single-token kernels."""
        fn = self._spec_fns.get(span)
        if fn is None:
            if self._spec_fns or self._fused_fns or self._decode_fns:
                self.stats.rejits += 1
            cfg = self.cfg
            if self.kv_layout == "paged":
                def _verify(p, pool, c, t, live, eos, bud):
                    return model_lib.spec_verify_paged(
                        cfg, p, pool, c, t, live, eos, bud)
                fn = jax.jit(_verify, donate_argnums=(1, 2))
            else:   # "auto" (contiguous engines resolve spec to off)
                def _verify(p, c, pool, t, up, live, eos, bud):
                    return model_lib.spec_verify_mixed(
                        cfg, p, c, pool, t, up, live, eos, bud)
                fn = jax.jit(_verify, donate_argnums=(1, 2))
            self._spec_fns[span] = fn
        return fn

    def _select_spec(self, n_active: int
                     ) -> Tuple[int, Optional[Tuple], Optional[str]]:
        """Resolve this step's speculative verify span (0 = off) and,
        in auto mode, its VPE bucket + variant name.

        Runs BEFORE horizon selection: a step that speculates REPLACES
        the fused-horizon call outright, so the decode_horizon axis
        sees no sample that step (its trial accounting never dangles on
        a call that didn't run) and plain-vs-speculative compete only
        through the spec axis's own off-vs-span record.  The bucket
        extends the horizon axis's queue-depth × occupancy key with the
        engine's measured accept-rate level — the workload dimension
        that decides whether a wider verify pass pays."""
        if self._rung_demoted("spec"):
            # runtime demotion: speculation off for the probation
            # window, no spec-axis sample recorded (the off-variant
            # feed stays honest — no fault-window walls pollute it)
            return 0, None, None
        if self.spec_draft == "off":
            return 0, None, None
        if self.spec_draft != "auto":
            return int(self.spec_draft), None, None
        if self.vpe is None:
            return 0, None, None
        bucket = spec_accept_bucket(len(self.queue), n_active,
                                    self.num_slots, self._accept_ema,
                                    levels=self.occupancy_levels)
        if self.slo_weight > 0:
            bucket = bucket + self._slo_bucket()
        bucket = bucket + self._shard_tail
        name = self.vpe.controller.select("spec_draft", bucket)
        return (0 if name == "off" else int(name)), bucket, name

    def _update_accept_ema(self, offered: int, accepted: int) -> None:
        # acceptance is a workload property, not a timing — compile
        # taint doesn't corrupt it, so the EMA always updates
        if not offered:
            return
        frac = accepted / offered
        self._accept_ema = (frac if self._accept_ema is None
                            else 0.8 * self._accept_ema + 0.2 * frac)

    def _probe_accept(self, i: int, new_toks: List[int]) -> int:
        """Counterfactual accept count on the PLAIN path: how many of
        this call's committed tokens the proposer WOULD have drafted.

        The accept-rate level is part of the spec axis's dispatch key,
        but real accept measurements only happen while speculating — if
        the EMA froze whenever the axis selected "off", a bucket that
        concluded "off" against a cold table could never discover the
        table has since warmed (the information arrow would point one
        way).  Drafting is deterministic host-side table lookups, so
        the plain path can measure the exact counterfactual for free:
        draft against the pre-commit context and count the longest
        matching prefix, the same longest-prefix rule the verify mask
        applies on device.  Keeps the EMA live in both directions with
        zero device cost and no output influence."""
        drafts = self.proposer.draft(i, len(new_toks))
        e = 0
        while e < len(new_toks) and drafts[e] == new_toks[e]:
            e += 1
        return e

    def _spec_decode(self, S: int, sbucket, sname,
                     remaining: Dict[int, int], t_h: float) -> None:
        """One speculative verify call: reserve pages for the full
        S-position candidate span, draft S-1 tokens per live slot from
        the n-gram table, run the one-pass verify, fence once, replay
        the committed prefixes, roll rejected-tail pages back and
        retire stopped slots.  Structure mirrors :meth:`_fused_decode`;
        the differences are the host-built (slots, S) token block (the
        drafts) and the accept-rate accounting that feeds the spec
        axis's bucket level."""
        fault = self._take_fault("spec")
        bt_jits = self._bt_jit_cache_size()
        if self.pages is not None:
            self._grow_block_tables(span=S, remaining=remaining)
            remaining = {i: r for i, r in remaining.items()
                         if self.slots[i].req is not None
                         and not self.slots[i].prefilling}
            if not remaining:
                return
            self._refresh_device_masks()
        if fault is not None and fault.kind == "device":
            # verify pass raised before dispatch: reservations rolled
            # back, spec demoted to off — next steps run plain decode
            self._span_device_fault(
                "spec", list(remaining),
                "injected device fault in speculative verify call")
            return
        n_active = len(remaining)
        # host-side drafting: column 0 is the slot's committed last
        # token (the verify input contract — its score is the token a
        # plain decode step would emit), columns 1..S-1 the candidates.
        # Misses pad with a deliberately-wrong token (see NGramProposer)
        # so speculation measures as a loss where the table is cold.
        tokens = np.zeros((self.num_slots, S), np.int32)
        for i, slot in enumerate(self.slots):
            if slot.req is not None:
                tokens[i, 0] = slot.tok
            if i in remaining:
                tokens[i, 1:] = self.proposer.draft(i, S - 1)
        fn = self._spec_fn(S)
        jits = fn._cache_size()
        budget = np.zeros((self.num_slots,), np.int32)
        for i, rem in remaining.items():
            budget[i] = rem
        bud_dev = jnp.asarray(budget)
        tok_dev = jnp.asarray(tokens)
        t0 = time.perf_counter()
        if self.kv_layout == "paged":
            self.page_pool, cache, tok_block, valid, final_tok = fn(
                self.params, self.page_pool, self.cache, tok_dev,
                self._live_dev, self._eos_dev, bud_dev)
        else:
            cache, self.page_pool, tok_block, valid, final_tok = fn(
                self.params, self.cache, self.page_pool, tok_dev,
                self._use_paged_dev, self._live_dev, self._eos_dev, bud_dev)
        toks, tripped = self._guarded_fence(tok_block, fault)
        emits = np.asarray(valid)
        dt = time.perf_counter() - t0
        self.cache = cache
        self._tok_dev = final_tok
        self.stats.decode_s += dt
        self.stats.decode_steps += 1
        self.stats.spec_calls += 1
        if tripped:
            # verify-pass fence stalled: commit the (late) tokens but
            # demote speculation — its span is the one that hung
            self._demote("spec")
        if fault is not None and fault.kind == "nan":
            toks = toks.copy()
            rows = ([fault.slot] if fault.slot is not None
                    and fault.slot in remaining else list(remaining))
            toks[rows, :] = -1
        step_tainted = fn._cache_size() != jits
        if self._bt_jit_cache_size() != bt_jits:
            step_tainted = True     # a splice jit compiled inside t_h
        if tripped:
            step_tainted = True     # a stalled wall must not feed axes
        if step_tainted:
            self.stats.tainted_steps += 1
        valid_total = int(emits.sum())
        share = dt / max(valid_total, 1)
        vocab = self.cfg.vocab_size
        quarantine: List[int] = []
        offered_total = accepted_total = 0
        for i in remaining:
            slot = self.slots[i]
            # committed tokens are a contiguous prefix of the span
            # (match, budget and EOS masks are all prefixes)
            e = int(emits[i].sum())
            new_toks = [int(t) for t in toks[i, :e]]
            if any(t < 0 or t >= vocab for t in new_toks):
                # poisoned verify logits: commit nothing from this call
                # for the slot, quarantine it after the replay loop
                quarantine.append(i)
                continue
            # drafts this slot's budget could still have committed
            # (committing k drafts needs k+1 <= budget), vs the drafts
            # that actually landed (everything before the correction)
            offered = min(S - 1, max(remaining[i] - 1, 0))
            acc = max(e - 1, 0)
            offered_total += offered
            accepted_total += acc
            self.stats.draft_tokens += offered
            self.stats.accepted_tokens += acc
            self.stats.accept_hist[acc] = \
                self.stats.accept_hist.get(acc, 0) + 1
            slot.req.out.extend(new_toks)
            self.proposer.observe(i, new_toks)
            slot.tok = new_toks[-1]
            slot.pos += e
            slot.steps_resident += e
            if not step_tainted:
                slot.clean_step_shares.extend([share] * e)
            self.stats.tokens_out += e
            if slot.layout == "paged":
                self._rollback_reserved(i)
            self._retire_if_done(i)
        for i in quarantine:
            self._numeric_fault(
                i, "out-of-range token from speculative verify call")
        self._update_accept_ema(offered_total, accepted_total)
        if fault is None and not tripped:
            self._tick_probation()
        if self.vpe is not None and sbucket is not None \
                and not step_tainted and valid_total:
            # per-COMMITTED-token wall of the full span (drafting +
            # reservation + call + fence + replay): a span whose drafts
            # miss commits ~1 token per call and prices itself out —
            # the same self-pricing contract as the horizon axis, with
            # the accept rate doing the work the freeze mask does there
            charge = 1.0 + self.slo_weight * self._queue_pressure()
            self.vpe.profiler.record("spec_draft", sname, sbucket,
                                     (time.perf_counter() - t_h)
                                     / valid_total * charge)
            self.vpe.controller.on_sample("spec_draft", sbucket, sname)

    def step(self) -> bool:
        """One engine iteration; returns False when fully idle.

        The interleaved pipeline: admission (placement-only for paged
        slots) and at most ``chunks_per_step`` prefill chunks run first,
        then ONE decode step advances the decoding slots — so the wall
        between two decode steps is bounded by the chunk budget, not by
        the longest queued prompt (``stats.decode_stall_s`` records that
        bound being exercised).

        On a tensor-sharded mesh (``mp > 1``) the iteration runs with
        the mesh set, which is how the paged Pallas kernels find the
        head axis to ``shard_map`` over (``kvcache._per_head_shard``)."""
        if self.mesh_shape[1] == 1:
            return self._step()
        with jax.set_mesh(self.mesh):
            return self._step()

    def _step(self) -> bool:
        self._shed_expired()     # deadline sweep at the step boundary
        had_decoders = self.num_decoding > 0
        admits_before = len(self.stats.queue_wait_s)
        t_p = time.perf_counter()
        self._admit()
        ran_chunk = self._run_prefill_chunks()
        prefill_work = (ran_chunk
                        or len(self.stats.queue_wait_s) != admits_before)
        n_active = self.num_decoding
        if n_active == 0:
            # prefill-only step (every occupied slot mid-chunk), or idle
            return prefill_work
        if had_decoders and prefill_work:
            # decode service interruption imposed by this step's
            # admission + chunk phase on already-resident requests
            self.stats.decode_stall_s.append(time.perf_counter() - t_p)
        # the horizon axis's timed span starts HERE: it must include the
        # per-call host work a fused horizon amortizes (remaining/budget
        # builds, mask refresh, page reservation, the replay loop) — the
        # fenced device wall alone is nearly horizon-independent per
        # token, and feeding only that would hide exactly the overhead
        # the axis exists to measure
        t_h = time.perf_counter()
        # speculative decoding is tried FIRST: a step that speculates
        # replaces the fused-horizon call outright (one verify pass IS
        # this step's decode), so horizon selection below never runs
        # that step and neither axis records a sample for a call that
        # didn't happen.  When the spec axis is live but resolves to
        # "off" (or the span is clamped out by tiny budgets), the plain
        # path runs and feeds the spec axis as the off variant.
        self._spec_off_pending = None
        S, sbucket, sname = self._select_spec(n_active)
        if S > 1 or sbucket is not None:
            remaining = {i: s.req.max_new_tokens - len(s.req.out)
                         for i, s in enumerate(self.slots)
                         if s.req is not None and not s.prefilling}
            if S > 1:
                # same declared-set clamp as the horizon path: an
                # arbitrary clamped span would pay a fresh trace+compile
                cap = pad_to_bucket(max(remaining.values()), minimum=1)
                choices = (self.spec_choices if self.spec_draft == "auto"
                           else (int(self.spec_draft),))
                fit = [c for c in choices if c <= S and c <= cap]
                S = max(fit) if fit else 0
            if S > 1:
                self._spec_decode(S, sbucket, sname, remaining, t_h)
                return True
            if sbucket is not None:
                self._spec_off_pending = (sbucket, sname)
        H, hbucket, hname = self._select_horizon(n_active)
        if H > 1:
            # tokens each decoding slot may still emit (host-known): the
            # fused call's in-graph budget, and the horizon clamp —
            # fusing past every slot's budget would only burn frozen
            # steps.  Built only here: the H=1 hot path never reads it.
            remaining = {i: s.req.max_new_tokens - len(s.req.out)
                         for i, s in enumerate(self.slots)
                         if s.req is not None and not s.prefilling}
            # clamp to the largest remaining budget, flooring onto the
            # DECLARED horizon set ({1, H} for a fixed horizon, {1} ∪
            # horizon_choices for auto): an arbitrary clamped length
            # would pay a fresh trace+compile mid-serve, which costs
            # more than the frozen steps it avoids
            cap = pad_to_bucket(max(remaining.values()), minimum=1)
            allowed = [1] + [c for c in
                             (self.horizon_choices
                              if self.decode_horizon == "auto" else (H,))
                             if c <= H]
            H = max(c for c in allowed if c <= cap)
        self._refresh_device_masks()
        if H > 1:
            self._fused_decode(H, hbucket, hname, remaining, t_h)
            return True
        # -- classic single-token step (the horizon-1 incumbent) ----------
        fault = self._take_fault("decode")
        bt_jits = self._bt_jit_cache_size()
        if self.pages is not None:
            self._grow_block_tables()
            n_active = self.num_decoding
            if n_active == 0:
                return True     # growth preempted every decoder
            self._refresh_device_masks()
        # serve_decode_impl is selected per occupancy × mesh shape: the
        # winning attention layout on one device need not win sharded
        bucket = occupancy_bucket(n_active, self.num_slots,
                                  levels=self.occupancy_levels) \
            + self._shard_tail
        fn = self._decode_fn(bucket)
        if fault is not None and fault.kind == "device":
            # raised before dispatch (donated buffers unconsumed).
            # Blame the kernel variant only when one actually ran: the
            # grouped incumbent has no rung below it, so its faults
            # charge the touched requests instead.
            resolved = self._resolve_impl(self._last_variant)
            rung = ("decode_pallas"
                    if resolved in kvcache.PAGED_KERNEL_IMPLS else None)
            touched = [i for i, s in enumerate(self.slots)
                       if s.req is not None and not s.prefilling]
            self._span_device_fault(
                rung, touched, "injected device fault in decode step")
            return True
        decode_jits = fn._cache_size()
        t0 = time.perf_counter()
        if self.kv_layout == "paged":
            self.page_pool, cache, next_tok = fn(
                self.params, self.page_pool, self.cache, self._tok_dev,
                self._live_dev)
        elif self.kv_layout == "auto":
            cache, self.page_pool, next_tok = fn(
                self.params, self.cache, self.page_pool, self._tok_dev,
                self._use_paged_dev, self._live_dev)
        else:
            cache, next_tok = fn(self.params, self.cache, self._tok_dev)
        toks, tripped = self._guarded_fence(next_tok, fault)
        dt = time.perf_counter() - t0
        self.cache = cache
        self._tok_dev = next_tok     # next step's input, already on device
        self.stats.decode_s += dt
        self.stats.decode_steps += 1
        self.stats.horizon_hist[1] = self.stats.horizon_hist.get(1, 0) + 1
        if tripped and self._resolve_impl(self._last_variant) \
                in kvcache.PAGED_KERNEL_IMPLS:
            # a kernel-backed step hung the fence: demote to grouped.
            # A grouped stall has nothing to demote to — it is counted
            # (watchdog_trips) and survives as a tainted step.
            self._demote("decode_pallas")
        if fault is not None and fault.kind == "nan":
            toks = toks.copy()
            if fault.slot is not None:
                toks[fault.slot] = -1
            else:
                toks[:] = -1
        # a step whose wall includes a decode-jit trace+compile must not
        # feed the per-slot attribution (decode shapes are static here,
        # so compiles happen exactly when a variant is first baked in —
        # the jit-cache growth check also catches any recompile)
        step_tainted = fn._cache_size() != decode_jits
        if self._bt_jit_cache_size() != bt_jits:
            step_tainted = True     # a splice jit compiled inside t_h
        if tripped:
            step_tainted = True     # a stalled wall must not feed axes
        if step_tainted:
            self.stats.tainted_steps += 1
        if self.vpe is not None and not tripped:
            self.vpe.profiler.record(self._axis, self._last_variant, bucket, dt)
            self.vpe.controller.on_sample(self._axis, bucket, self._last_variant)
        share = dt / n_active
        vocab = self.cfg.vocab_size
        quarantine: List[int] = []
        probe_off = probe_acc = 0
        self._probe_tick += 1
        probing = (self.spec_draft == "auto"
                   and self._probe_tick % self._probe_every == 0)
        for i, slot in enumerate(self.slots):
            if slot.req is None or slot.prefilling:
                continue   # free/prefilling slot decoded garbage; discard
            t = int(toks[i])
            if t < 0 or t >= vocab:
                # always-on numeric validation (see _fused_decode)
                quarantine.append(i)
                continue
            slot.tok = t
            slot.pos += 1
            slot.steps_resident += 1
            if not step_tainted:
                slot.clean_step_shares.append(share)
            slot.req.out.append(t)
            if self.proposer is not None:
                if probing:
                    probe_acc += self._probe_accept(i, [t])
                    probe_off += 1
                self.proposer.observe(i, [t])
            self.stats.tokens_out += 1
            self._retire_if_done(i)
        for i in quarantine:
            self._numeric_fault(i, "out-of-range token from decode step")
        self._update_accept_ema(probe_off, probe_acc)
        if fault is None and not tripped:
            self._tick_probation()
        if self.vpe is not None and hbucket is not None and not step_tainted:
            # the horizon axis optimizes the per-TOKEN wall of the FULL
            # step span (host bookkeeping + device call + replay): one
            # step at occupancy n_active emitted n_active tokens.  Same
            # SLO charge factor as the fused path so the two variants
            # compete under the same objective.
            charge = 1.0 + self.slo_weight * self._queue_pressure()
            self.vpe.profiler.record("decode_horizon", hname, hbucket,
                                     (time.perf_counter() - t_h) / n_active
                                     * charge)
            self.vpe.controller.on_sample("decode_horizon", hbucket, hname)
        if self.vpe is not None and self._spec_off_pending is not None \
                and not step_tainted:
            # same off-variant feed as the fused path: one step at
            # occupancy n_active emitted n_active tokens
            sb, sn = self._spec_off_pending
            charge = 1.0 + self.slo_weight * self._queue_pressure()
            self.vpe.profiler.record("spec_draft", sn, sb,
                                     (time.perf_counter() - t_h) / n_active
                                     * charge)
            self.vpe.controller.on_sample("spec_draft", sb, sn)
        return True

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drain queue + slots; returns completed requests."""
        steps = 0
        while self.queue or self.num_active > 0:
            if not self.step():
                break
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.completed


def _merge_stats(parts: Sequence[ServeStats]) -> ServeStats:
    """Aggregate per-replica stats into one :class:`ServeStats` view:
    scalars sum, series concatenate, histograms merge by key."""
    out = ServeStats()
    for p in parts:
        for f in dataclasses.fields(ServeStats):
            mine, theirs = getattr(out, f.name), getattr(p, f.name)
            if isinstance(mine, list):
                mine.extend(theirs)
            elif isinstance(mine, dict):
                for k, v in theirs.items():
                    mine[k] = mine.get(k, 0) + v
            else:
                setattr(out, f.name, mine + theirs)
    return out


class EngineReplicaGroup:
    """``dp`` independent engine replicas sharing one admission queue.

    The mesh's ``dp`` axis is *replica* parallelism: each replica is a
    full :class:`ContinuousBatchingEngine` holding its own parameter
    copy, slot pool, KV storage and page pool on its own ``(1, mp)``
    row of the device mesh — replicas never exchange activations, so
    the whole group is plain in-process objects (no RPC, no collective
    across ``dp``).  What they DO share is admission: one group-level
    queue feeds whichever replica has free capacity, so a burst lands
    on idle replicas instead of queueing behind a busy one.

    Dispatch semantics (:meth:`step`): while the shared queue is
    non-empty and some replica has a free slot, the best-priority
    (then oldest) queued request moves to the least-loaded replica.
    Requests are committed to a replica only when it can actually admit
    them — early binding would recreate per-replica head-of-line
    blocking, which is the thing a shared queue exists to avoid.
    Prefix-affinity and starvation bounds then apply *within* the
    replica exactly as on a single engine.

    Every replica is constructed with the full ``(dp, mp)``
    ``shard_dims``, so all replicas' dispatch keys carry the same
    shard segment and a shared ``vpe`` learns ONE policy per mesh
    configuration from every replica's samples.

    **Failover (PR 10).** The group is the recovery rung ABOVE the
    engine's degradation ladder: a replica that keeps producing fault
    evidence (terminal device/numeric step faults, repeated watchdog
    fence trips, dispatch losses) past ``replica_fault_budget`` since
    its last clean window is quarantined — its resident requests are
    preempted (exact greedy resume via ``effective_prompt``) and its
    queue drained back to the shared queue at class head, so survivors
    rerun identically on healthy replicas.  A quarantined replica is
    probed with canary requests (synthetic, excluded from
    :attr:`completed`); one clean canary run re-admits it and resets
    its evidence base.  A single shared :class:`FaultPlan` drives the
    whole group — replicas consume sites in deterministic step order,
    so group chaos runs replay exactly."""

    def __init__(self, cfg: ModelConfig, params: Any, *, dp: int, mp: int,
                 replica_fault_budget: int = 3,
                 **engine_kwargs: Any) -> None:
        if dp < 2:
            raise ValueError("EngineReplicaGroup needs dp >= 2 "
                             "(a single replica is just the engine)")
        if replica_fault_budget < 1:
            raise ValueError("replica_fault_budget must be >= 1")
        need = dp * mp
        devs = jax.devices()
        if len(devs) < need:
            raise ValueError(
                f"mesh ({dp},{mp}) needs {need} devices, only "
                f"{len(devs)} visible (set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N on CPU)")
        self.mesh_shape = (dp, mp)
        self.queue: List[Request] = []
        self._failed: List[Request] = []
        self._stats = ServeStats()   # group-level failures + failover events
        # the plan is SHARED with every replica (not copied): sites are
        # consumed in group-step order, one deterministic schedule.  The
        # admission bound guards the SHARED queue; replica-local queues
        # are dispatch buffers bounded by free slots, so the engines get
        # no depth bound of their own.
        self.faults: Optional[FaultPlan] = engine_kwargs.get("fault_plan")
        self.max_queue_depth: Optional[int] = \
            engine_kwargs.pop("max_queue_depth", None)
        if self.max_queue_depth is not None and self.max_queue_depth < 1:
            raise ValueError("max_queue_depth must be >= 1")
        self.max_request_faults: int = \
            engine_kwargs.get("max_request_faults", 3)
        self.replica_fault_budget = replica_fault_budget
        self.engines = [
            ContinuousBatchingEngine(
                cfg, params, mesh_shape=(1, mp),
                mesh_devices=devs[r * mp:(r + 1) * mp],
                shard_dims=(dp, mp), **engine_kwargs)
            for r in range(dp)
        ]
        self.max_len = self.engines[0].max_len
        self.quarantined: set = set()
        # per-replica evidence floor: fault evidence BELOW the floor was
        # already acted on (a quarantine or a clean canary resets it)
        self._ev_base = [0] * dp
        self._dispatch_faults = [0] * dp
        # replica -> (in-flight canary, evidence snapshot at launch)
        self._canary: Dict[int, Tuple[Request, int]] = {}
        self._canary_seq = 0
        self._deadlines_live = False

    # -- request intake ----------------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue on the SHARED queue — or terminally fail, with the
        same taxonomy as the single engine (reason code + detail)."""
        req.submit_t = time.perf_counter()
        err = _intake_error(req, self.max_len)
        if err is not None:
            self._fail(req, "intake", err)
            return
        if self.max_queue_depth is not None \
                and len(self.queue) >= self.max_queue_depth:
            self._fail(
                req, "capacity",
                f"queue depth {len(self.queue)} at admission bound "
                f"{self.max_queue_depth}")
            return
        if req.deadline_s is not None:
            self._deadlines_live = True
        req.status = "queued"
        self.queue.append(req)

    def _fail(self, req: Request, reason: str, detail: str) -> None:
        """Group-side terminal failure — same accounting contract as the
        engine's ``_fail_request`` for a request not resident anywhere:
        reason code, detail, ``done_t``, and a terminal queue wait when
        it was never admitted by any replica."""
        assert reason in FAIL_REASONS, reason
        req.swap = None
        req.error = reason
        req.error_detail = detail
        req.status = "failed"
        req.done = True
        req.done_t = time.perf_counter()
        if req.admit_step < 0:
            req.queue_wait_s = req.done_t - req.submit_t
            self._stats.rejected += 1
        self._stats.failed_by_reason[reason] = \
            self._stats.failed_by_reason.get(reason, 0) + 1
        self._failed.append(req)

    # -- replica failover ---------------------------------------------------
    def _evidence(self, r: int) -> int:
        """Cumulative fault evidence against replica *r*: step-level
        faults its own ladder absorbed or failed on, watchdog fence
        trips, and dispatch losses."""
        s = self.engines[r].stats
        return (s.device_faults + s.numeric_faults + s.watchdog_trips
                + self._dispatch_faults[r])

    def _quarantine_replica(self, r: int) -> None:
        """Pull replica *r* out of dispatch and migrate its work.

        Resident requests are preempted with swap DISABLED — a host
        swap image from a faulting replica is exactly as untrusted as
        its KV — and, with everything the replica had queued, drained
        back to the shared queue at class head (``_requeue`` ordering:
        ahead of their own class, behind better classes).  Greedy
        parity makes the rerun on a survivor token-exact.  Each
        migrated request is charged one fault so a request that keeps
        landing on dying replicas terminates as ``replica_lost``
        instead of migrating forever."""
        eng = self.engines[r]
        self.quarantined.add(r)
        self._stats.replica_quarantines += 1
        swap_save, eng.swap = eng.swap, False
        try:
            for i, s in enumerate(eng.slots):
                if s.req is not None:
                    eng._preempt_slot(i)
        finally:
            eng.swap = swap_save
        migrated = [q for q in eng.queue if not q.canary]
        eng.queue = [q for q in eng.queue if q.canary]
        for req in migrated:
            req.faults += 1
            if req.faults >= self.max_request_faults:
                self._fail(
                    req, "replica_lost",
                    f"fault budget spent migrating off replica {r}")
            else:
                req.status = "queued"
                self._requeue_shared(req)

    def _requeue_shared(self, req: Request) -> None:
        """Class-head insert into the SHARED queue (the group analogue
        of the engine's ``_requeue``)."""
        rank = PRIORITY_RANK[req.priority]
        pos = next((j for j, r in enumerate(self.queue)
                    if PRIORITY_RANK[r.priority] >= rank), len(self.queue))
        self.queue.insert(pos, req)

    def _check_replicas(self) -> None:
        for r in range(len(self.engines)):
            if r not in self.quarantined \
                    and self._evidence(r) - self._ev_base[r] \
                    >= self.replica_fault_budget:
                self._quarantine_replica(r)

    def _probe_quarantined(self) -> None:
        """Canary lifecycle: every quarantined replica always has one
        probe in flight.  A canary that completes ``ok`` with NO new
        fault evidence since launch re-admits the replica (evidence
        base reset — it starts its next budget window clean); a canary
        that fails or trips anything leaves the replica quarantined and
        a fresh probe launches next group step."""
        for r in sorted(self.quarantined):
            eng = self.engines[r]
            probe = self._canary.get(r)
            if probe is None:
                self._canary_seq -= 1
                can = Request(
                    rid=self._canary_seq,
                    prompt=list(range(1, 9)),
                    max_new_tokens=4,
                    priority="interactive",
                    canary=True)
                self._canary[r] = (can, self._evidence(r))
                self._stats.canary_probes += 1
                eng.submit(can)
                continue
            can, ev0 = probe
            if not can.done:
                continue
            del self._canary[r]
            if can.status == "done" and self._evidence(r) == ev0:
                self.quarantined.discard(r)
                self._ev_base[r] = self._evidence(r)
                self._stats.replica_readmissions += 1

    def _shed_expired(self) -> None:
        """Deadline sweep over the SHARED queue (replicas sweep their
        own queues and slots in their step)."""
        if not self._deadlines_live:
            return
        now = time.perf_counter()

        def _expired(q: Request) -> bool:
            return (q.deadline_s is not None
                    and now - q.submit_t > q.deadline_s)

        expired = [q for q in self.queue if _expired(q)]
        if expired:
            self.queue = [q for q in self.queue if not _expired(q)]
            for q in expired:
                self._fail(
                    q, "deadline",
                    f"expired in shared queue after {now - q.submit_t:.3f}s "
                    f"(deadline {q.deadline_s:.3f}s)")

    def _dispatch(self) -> None:
        """Move queued requests onto replicas with free capacity: best
        priority class first (FIFO within a class), least-loaded replica
        first.  Appends to the replica's queue directly — validation and
        ``submit_t`` already happened at group intake, and the replica's
        own scheduler handles it from here.

        Capacity is free slots MINUS requests already parked on the
        replica's local queue: dispatched-but-not-yet-admitted requests
        hold their claim, otherwise every tie-break in one dispatch pass
        would land on the same replica and a burst would serialize
        behind it — exactly the head-of-line blocking the shared queue
        exists to avoid.  Quarantined replicas take no dispatch; a
        ``dispatch``-site fault loses the handoff (the request stays in
        the shared queue, charged one fault) and counts as evidence
        against the target replica."""
        while self.queue:
            cap = [(sum(1 for s in e.slots if s.free) - len(e.queue), -r, e)
                   for r, e in enumerate(self.engines)
                   if r not in self.quarantined]
            if not cap:
                return              # every replica quarantined
            cap.sort(reverse=True)
            n_free, neg_r, target = cap[0]
            if n_free <= 0:
                return
            j = min(range(len(self.queue)),
                    key=lambda i: (PRIORITY_RANK[self.queue[i].priority], i))
            fault = (self.faults.take("dispatch")
                     if self.faults is not None else None)
            if fault is not None:
                req = self.queue[j]
                self._dispatch_faults[-neg_r] += 1
                req.faults += 1
                if req.faults >= self.max_request_faults:
                    self.queue.pop(j)
                    self._fail(req, "replica_lost",
                               "fault budget spent on lost dispatches")
                return              # retry the handoff next group step
            target.queue.append(self.queue.pop(j))

    # -- engine surface ----------------------------------------------------
    def step(self) -> bool:
        """One group iteration: shed expired, dispatch, step every
        replica that has work, evaluate replica health, run the canary
        lifecycle.  Returns False when the whole group is idle."""
        self._shed_expired()
        self._probe_quarantined()
        self._dispatch()
        progress = False
        for eng in self.engines:
            if eng.queue or eng.num_active > 0:
                progress = eng.step() or progress
        self._check_replicas()
        self._probe_quarantined()
        return progress or bool(self.queue)

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drain the shared queue and every replica; returns completed
        requests (failures included), exactly like the engine's.  A
        quarantined replica keeps the loop alive until its canary
        re-admits it, so a drained group ends healthy."""
        steps = 0
        while self.queue or self.quarantined \
                or any(e.queue or e.num_active > 0 for e in self.engines):
            if not self.step():
                break
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.completed

    def check_kv(self) -> None:
        """Page audit on every replica (replicas share no pages, so the
        group audit is the conjunction of the per-replica audits)."""
        for eng in self.engines:
            eng.check_kv()

    @property
    def num_active(self) -> int:
        return sum(e.num_active for e in self.engines)

    @property
    def completed(self) -> List[Request]:
        out: List[Request] = list(self._failed)
        for eng in self.engines:
            out.extend(r for r in eng.completed if not r.canary)
        return out

    @property
    def stats(self) -> ServeStats:
        """Aggregated view: per-replica stats merged plus group-level
        intake rejections.  Recomputed per access — cheap at bench
        scale, always consistent."""
        return _merge_stats([self._stats] + [e.stats for e in self.engines])


def make_serve_engine(cfg: ModelConfig, params: Any, *,
                      mesh_shape: Tuple[int, int] = (1, 1),
                      **engine_kwargs: Any):
    """Build the serve engine for a ``(dp, mp)`` mesh shape.

    ``dp == 1`` returns a plain :class:`ContinuousBatchingEngine`
    (sharded over ``mp`` when ``mp > 1``; the bitwise-identical
    single-device engine at ``(1, 1)``); ``dp > 1`` returns an
    :class:`EngineReplicaGroup` of dp single-row engines behind one
    shared admission queue.  Both expose the same serve surface
    (``submit`` / ``step`` / ``run`` / ``check_kv`` / ``completed`` /
    ``stats``)."""
    dp, mp = (int(mesh_shape[0]), int(mesh_shape[1]))
    if dp <= 1:
        return ContinuousBatchingEngine(cfg, params, mesh_shape=(1, mp),
                                        **engine_kwargs)
    return EngineReplicaGroup(cfg, params, dp=dp, mp=mp, **engine_kwargs)
