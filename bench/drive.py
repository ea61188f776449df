"""The client side of a run: sending requests, stepping the engine,
stamping every token the moment the client can see it.

The harness runs one thread.  Each iteration it sends every request
whose due time has passed, calls ``engine.step()`` once, and stamps the
tokens that the step delivered.  Latency is taken from a request's due
time, not from when it was sent, so a late generator is counted against
the system and reported apart (:attr:`Client.send_lag`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from bench import traffic


@dataclasses.dataclass
class Record:
    """One request as the client sees it (clock: ``time.perf_counter``)."""
    spec: traffic.Spec
    req: object                  # the program's Request
    due: float
    sent: float
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return self.spec.prompt_len

    @property
    def admitted(self) -> Optional[float]:
        """When the engine admitted it: its own queue-wait span after
        the moment it was sent, or None while it waits."""
        if self.req.admit_step < 0:
            return None
        return self.req.submit_t + self.req.queue_wait_s

    @property
    def finished(self) -> bool:
        return self.req.status == "done"

    @property
    def failed(self) -> bool:
        return self.req.status == "failed"


@dataclasses.dataclass
class StepWork:
    """What one engine step computed, for the work-based metrics of a
    traced run: prefill chunks as (base, length) and, per decoding
    request, the key counts of the tokens it produced by decode, in the
    order of the fused call's steps."""
    t0: float
    t1: float
    chunks: List[tuple] = dataclasses.field(default_factory=list)
    decode: List[List[int]] = dataclasses.field(default_factory=list)
    final_chunks: int = 0


class Client:
    """Sends requests to one engine and stamps what comes back."""

    def __init__(self, engine, request_cls, vocab: int, seed: int,
                 clock: Callable[[], float] = time.perf_counter) -> None:
        self.engine = engine
        self.request_cls = request_cls
        self.vocab = vocab
        self.seed = seed
        self.clock = clock
        self.records: Dict[int, Record] = {}
        self.inflight: List[Record] = []
        self.send_lag: List[float] = []
        # (time, requests that gained a decoded token) for every step
        self.decode_rows: List[tuple] = []

    def send(self, spec: traffic.Spec, due: float) -> Record:
        prompt = traffic.prompt_tokens(self.seed, spec.rid, spec.prompt_len,
                                       self.vocab)
        req = self.request_cls(rid=spec.rid, prompt=prompt,
                               max_new_tokens=spec.output_len)
        now = self.clock()
        self.engine.submit(req)
        rec = Record(spec, req, due, now)
        self.records[spec.rid] = rec
        self.inflight.append(rec)
        self.send_lag.append(max(0.0, now - due))
        return rec

    def stamp(self) -> None:
        """Give each token delivered since the last call the time now."""
        now = self.clock()
        keep, rows = [], 0
        for rec in self.inflight:
            n, seen = len(rec.req.out), len(rec.stamps)
            if n > seen:
                rec.stamps.extend([now] * (n - seen))
                rows += n > max(seen, 1)
            if not (rec.finished or rec.failed):
                keep.append(rec)
        self.inflight = keep
        self.decode_rows.append((now, rows))

    def queued(self) -> int:
        return sum(1 for r in self.inflight if r.req.status == "queued")

    def step(self, work: Optional[List[StepWork]] = None,
             chunk: int = 0) -> bool:
        """One engine step, then stamps.  With ``work`` given, what the
        step computed is appended to it (see :class:`WorkProbe`)."""
        probe = WorkProbe(self, chunk) if work is not None else None
        t0 = self.clock()
        with TraceAnnotation("bench.step"):
            busy = self.engine.step()
        self.stamp()
        if probe is not None:
            work.append(probe.finish(t0, self.clock()))
        return busy


class WorkProbe:
    """Reads what one step did from the engine's slots and the served
    tokens: prompt positions prefilled (per request, cut at the engine's
    chunk boundaries) and tokens produced by decode with the number of
    keys each attended to.  Only traced runs pay for this."""

    def __init__(self, client: Client, chunk: int) -> None:
        self.client = client
        self.chunk = chunk
        self.fill = self._fill()
        self.count = {id(r.req): len(r.req.out) for r in client.inflight}
        self.recs = list(client.inflight)

    def _fill(self) -> Dict[int, int]:
        out = {}
        for s in self.client.engine.slots:
            if s.req is not None:
                out[id(s.req)] = (s.fill_pos if s.prefilling
                                  else len(s.req.prompt))
        return out

    def finish(self, t0: float, t1: float) -> StepWork:
        work = StepWork(t0, t1)
        after = self._fill()
        for rec in self.recs:
            key = id(rec.req)
            P = rec.prompt_len
            n0, n1 = self.count[key], len(rec.req.out)
            f0 = self.fill.get(key, 0) if n0 == 0 else P
            f1 = P if n1 > 0 else after.get(key, f0)
            base = f0
            while base < f1:
                end = min(f1, (base // self.chunk + 1) * self.chunk
                          if self.chunk else f1)
                work.chunks.append((base, end - base))
                base = end
            if n0 == 0 and n1 > 0:
                work.final_chunks += 1
            first = max(n0, 1)
            if n1 > first:
                work.decode.append([P + j for j in range(first, n1)])
        return work


# -- the phases of a run -----------------------------------------------------

def warm_up(client: Client, waves: Sequence[Sequence[traffic.Spec]],
            cap_s: float = 600.0) -> None:
    """Send each wave at once and serve it to the end before the next."""
    for wave in waves:
        for spec in wave:
            client.send(spec, client.clock())
        t_end = client.clock() + cap_s
        while client.inflight and client.clock() < t_end:
            client.step()
        if client.inflight:
            raise RuntimeError("warm-up requests did not finish")


def ramp(client: Client, specs: Iterator[traffic.Spec], slots: int,
         depth: int) -> None:
    """Backlog set-up: fill every slot and the queue, and step until
    every slot holds a request that has its first token."""
    while len(client.inflight) < slots + depth:
        client.send(next(specs), client.clock())
    while sum(1 for r in client.inflight if r.req.out) < slots:
        client.step()
        top_up(client, specs, depth)


def top_up(client: Client, specs: Iterator[traffic.Spec], depth: int) -> None:
    while client.queued() < depth:
        client.send(next(specs), client.clock())


def backlog_window(client: Client, specs: Iterator[traffic.Spec],
                   depth: int, seconds: float, tracer=None) -> tuple:
    """Serve the backlog for ``seconds``; returns (start, end) with the
    end at the close of the first step past the deadline."""
    w0 = client.clock()
    end = w0 + seconds
    while client.clock() < end:
        top_up(client, specs, depth)
        if tracer is not None:
            tracer.tick(client)
        client.step(tracer.work if tracer and tracer.on else None,
                    tracer.chunk if tracer else 0)
    if tracer is not None:
        tracer.close(client)
    return w0, client.clock()


def poisson_window(client: Client, specs: Iterator[traffic.Spec],
                   seconds: float, drain_cap_s: float, tracer=None) -> tuple:
    """Send on schedule for ``seconds``, then keep sending and stepping
    until every request due in the window has finished or failed, or
    ``drain_cap_s`` has passed.  Returns (start, end of sending, end)."""
    w0 = client.clock()
    end = w0 + seconds
    window_rids = set()
    spec = next(specs)
    while True:
        now = client.clock()
        while w0 + spec.due <= now:
            client.send(spec, w0 + spec.due)
            if spec.due < seconds:
                window_rids.add(spec.rid)
            spec = next(specs)
        if now >= end:
            pending = [r for r in window_rids
                       if not (client.records[r].finished
                               or client.records[r].failed)]
            if not pending or now >= end + drain_cap_s:
                break
        if tracer is not None:
            tracer.tick(client)
        busy = client.step(tracer.work if tracer and tracer.on else None,
                           tracer.chunk if tracer else 0)
        if not busy:
            wait = w0 + spec.due - client.clock()
            if wait > 0:
                time.sleep(min(wait, 0.002))
    if tracer is not None:
        tracer.close(client)
    return w0, end, client.clock()


# -- what the client saw, reduced ---------------------------------------------

def gaps_between_tokens(recs: Sequence[Record], lo: float, hi: float
                        ) -> List[float]:
    """Every gap between successive tokens of one request whose later
    token the client saw in [lo, hi]."""
    out = []
    for r in recs:
        s = r.stamps
        out.extend(b - a for a, b in zip(s, s[1:]) if lo <= b <= hi)
    return out


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (linear, as numpy's default), or None."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
