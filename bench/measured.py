"""What one run measured, as the metric readers see it.

A reader (``bench/metrics/<metric>.py``) gets one :class:`Run` and
returns a number, or None where the run holds nothing to read.  The
helpers here are the reductions more than one reader needs: the
requests that count, the tokens the client saw, and the work a traced
sub-window held with the operations and bytes it needed.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bench import drive, flops
from bench.trace_reduce import Summary

# How a TPU trace names what the metrics read: a program by the function
# the engine jits (``jit__steps(<hash>)``: the fused horizon, or the single
# step ``_step``; ``jit__lambda(<hash>)``: the chunked prefill), a kernel
# by the jitted function that makes its custom call.
PROGRAMS = {"decode": r"^jit__steps?\(", "prefill": r"^jit__lambda\("}
KERNELS = {"decode": r"^paged_attention_pallas(\.\d+)?$",
           "prefill": r"^paged_prefill_attention_pallas(\.\d+)?$"}


@dataclasses.dataclass
class Run:
    workload: Dict            # the cell's entry in BENCHMARK.json
    config: Dict              # bench/configs/<config>.json
    mix: Dict                 # bench/traffic/<traffic>.json
    dims: Dict                # the reference's sizes of the configuration
    peaks: Dict               # bench/peaks.json entry of this device
    chips: int
    records: List[drive.Record]
    start: float              # the window opens
    send_end: float           # sending stops counting (= end for a backlog)
    end: float                # the window closes (after the drain)
    setup_s: float
    # (time, requests that gained a decoded token) for every step
    decode_rows: List[tuple] = dataclasses.field(default_factory=list)
    work: List[drive.StepWork] = dataclasses.field(default_factory=list)
    trace: Optional[Summary] = None

    # -- requests and tokens --------------------------------------------------

    def due_in_window(self) -> List[drive.Record]:
        """Requests due inside the window (every one counts in a tail)."""
        return [r for r in self.records
                if self.start <= r.due < self.send_end]

    def tokens_seen(self, lo: float, hi: float) -> int:
        return sum(sum(1 for t in r.stamps if lo <= t <= hi)
                   for r in self.records)

    def ttft(self, recs: Sequence[drive.Record]) -> List[float]:
        """Due time to first token; a request that has none when the
        run closes counts with the whole time it waited."""
        return [(r.stamps[0] if r.stamps else self.end) - r.due
                for r in recs]

    def queue_waits(self, recs: Sequence[drive.Record]) -> List[float]:
        """Due time to admission (the whole wait where never admitted)."""
        out = []
        for r in recs:
            t = r.admitted
            out.append((self.end if t is None else t) - r.due)
        return out

    def itl_gaps(self) -> List[float]:
        """The gaps between tokens that count: for a backlog every gap
        the window saw; for scheduled traffic every gap of every request
        due in the window, drained ones included."""
        if self.mix["kind"] == "backlog":
            return drive.gaps_between_tokens(self.records, self.start,
                                             self.end)
        return drive.gaps_between_tokens(self.due_in_window(),
                                         float("-inf"), float("inf"))

    def decode_occupancy(self) -> Optional[float]:
        """Requests that gained a decoded token, per step that decoded."""
        rows = [n for t, n in self.decode_rows
                if self.start <= t <= self.end and n > 0]
        return sum(rows) / len(rows) if rows else None

    # -- work in the traced sub-window ----------------------------------------

    @property
    def block(self) -> int:
        return int(self.config["engine"]["block_size"])

    def decode_kernel_need(self) -> Tuple[float, float, float]:
        """(flops, bytes, least seconds) the decode attention kernel
        needed over the traced steps: per fused step and layer, the live
        rows of that step together."""
        F = B = least = 0.0
        pf, pb = self.peaks["bf16_flops_per_s"], self.peaks["hbm_bytes_per_s"]
        for w in self.work:
            depth = max((len(d) for d in w.decode), default=0)
            for s in range(depth):
                rows = [d[s] for d in w.decode if len(d) > s]
                f, b = flops.decode_attention(rows, self.dims, self.block)
                F += f * self.dims["L"]
                B += b * self.dims["L"]
                least += self.dims["L"] * max(f / pf, b / pb)
        return F, B, least

    def prefill_kernel_need(self) -> Tuple[float, float, float]:
        """The same for the chunked-prefill attention kernel: one call
        per chunk and layer."""
        F = B = least = 0.0
        pf, pb = self.peaks["bf16_flops_per_s"], self.peaks["hbm_bytes_per_s"]
        for w in self.work:
            for base, n in w.chunks:
                f, b = flops.prefill_attention(base, n, self.dims, self.block)
                F += f * self.dims["L"]
                B += b * self.dims["L"]
                least += self.dims["L"] * max(f / pf, b / pb)
        return F, B, least

    def model_flops(self) -> float:
        """FLOPs the model needed for the tokens of the traced steps:
        prompt chunks at their context, the head once per finished
        prompt, and every decoded token at its context with its head."""
        total = 0.0
        for w in self.work:
            for base, n in w.chunks:
                total += flops.chunk_flops(base, n, self.dims)
            total += w.final_chunks * flops.head_flops(self.dims)
            for d in w.decode:
                for n_keys in d:
                    total += (flops.token_flops(n_keys, self.dims)
                              + flops.head_flops(self.dims))
        return total

    # -- the traced sub-window ------------------------------------------------

    def program_ms(self, kind: str) -> Optional[float]:
        if self.trace is None:
            return None
        n = self.trace.count(PROGRAMS[kind], of="module")
        if not n:
            return None
        return 1000.0 * self.trace.time(PROGRAMS[kind], of="module") / n

    def roofline(self, kind: str) -> Optional[float]:
        if self.trace is None:
            return None
        spent = self.trace.time(KERNELS[kind])
        need = (self.decode_kernel_need() if kind == "decode"
                else self.prefill_kernel_need())
        if spent <= 0 or need[2] <= 0:
            return None
        return 100.0 * need[2] / spent

    def idle_share(self) -> Optional[float]:
        return None if self.trace is None else 100.0 * self.trace.idle_share

    def step_mfu(self) -> Optional[float]:
        if self.trace is None or not self.work:
            return None
        peak = (self.trace.window_s * self.chips
                * self.peaks["bf16_flops_per_s"])
        return 100.0 * self.model_flops() / peak

