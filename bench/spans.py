"""The engine's own spans in a traced run: host time per step, and the
work counts the engine records.

The engine opens a ``jax.profiler`` span at each phase of a step
(``docs/tracing.md``): ``serve.step`` around the whole step, its phases
inside it, and a ``<phase>.wait`` span around every host block on a
device value; each phase carries its work counts as span stats.  They
are written into the profiler's own trace, on the clock of the device
events that ``trace_reduce`` reads.

This module reads them, stats kept, from the traced run's
``.xplane.pb`` with ``jax.profiler.ProfileData``, on the harness's
thread (the one that holds the ``bench.traced`` sub-window), and keeps
the steps that lie wholly inside the sub-window with the spans they
hold.  It reduces them to each step's duration, wait time (the union of
its ``.wait`` spans) and host self time (duration less wait), each
phase's self time (its duration less the spans nested in it), and the
stats.  Host time per step is the mean over the steps that had work.

A program without these spans leaves nothing to read: every reader
then returns None.
"""

from __future__ import annotations

import dataclasses
import functools
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce

STEP = "serve.step"
# the engine's phases (docs/tracing.md); each may hold "<phase>.wait"
PHASES = ("serve.step", "serve.admit", "serve.place", "serve.prefill_chunk",
          "serve.first_token", "serve.reserve", "serve.masks",
          "serve.decode", "serve.verify", "serve.replay")
WAIT = ".wait"
NAMES = frozenset(PHASES + tuple(p + WAIT for p in PHASES))
# serve.step's stats: the requests the step began with
WORK = ("queued", "decoding", "prefilling")


@dataclasses.dataclass
class Span:
    name: str
    start: float                      # seconds on the profiler's clock
    end: float
    stats: Dict[str, int] = dataclasses.field(default_factory=dict)
    self_s: float = 0.0               # duration less the spans nested in it
    inner: List["Span"] = dataclasses.field(default_factory=list)

    @property
    def duration_s(self) -> float:
        return self.end - self.start

    def nested(self) -> List["Span"]:
        """Every span inside this one, at any depth."""
        out = []
        for s in self.inner:
            out.append(s)
            out.extend(s.nested())
        return out


@dataclasses.dataclass
class Step:
    span: Span
    wait_s: float                     # union of the .wait spans it holds

    @property
    def self_s(self) -> float:
        """Host time of the step: its duration less its waits."""
        return self.span.duration_s - self.wait_s


@dataclasses.dataclass
class Engine:
    """The engine's spans over one window."""
    steps: List[Step]

    def spans(self, name: str) -> List[Span]:
        out = [st.span for st in self.steps if st.span.name == name]
        out += [s for st in self.steps for s in st.span.nested()
                if s.name == name]
        return out

    def phase_self_s(self) -> Dict[str, float]:
        """Self time by span name, summed over the steps, ``.wait``
        spans included: where spans nest, as on one thread, the values
        add up to the steps' whole duration."""
        out: Dict[str, float] = {}
        for st in self.steps:
            for s in [st.span] + st.span.nested():
                out[s.name] = out.get(s.name, 0.0) + s.self_s
        return out

    def host_ms_per_step(self) -> Optional[float]:
        """Mean host self time of the steps that began with a request
        queued or resident: an empty engine's step does no work, and an
        open loop between arrivals makes thousands of them."""
        busy = [st for st in self.steps
                if any(st.span.stats.get(k, 1) for k in WORK)]
        if not busy:
            return None
        return 1000.0 * sum(st.self_s for st in busy) / len(busy)

    def mean_stat(self, name: str, key: str) -> Optional[float]:
        values = [s.stats[key] for s in self.spans(name) if key in s.stats]
        return sum(values) / len(values) if values else None


def nest(spans: Sequence[Span]) -> List[Span]:
    """Give each span the spans inside it and its self time; returns the
    outermost ones.  Spans on one thread nest, so a span's parent is the
    innermost earlier span that still contains it (a span that overlaps
    its predecessor without lying inside it goes to an outer one)."""
    ordered = sorted(spans, key=lambda s: (s.start, -s.end))
    roots: List[Span] = []
    stack: List[Span] = []
    for s in ordered:
        while stack and not (s.start >= stack[-1].start
                             and s.end <= stack[-1].end):
            stack.pop()
        (stack[-1].inner if stack else roots).append(s)
        stack.append(s)
    for s in ordered:
        held = trace_reduce.union(((c.start, c.end) for c in s.inner),
                                  s.start, s.end)
        s.self_s = s.duration_s - trace_reduce.length(held)
    return roots


def reduce(spans: Sequence[Span], lo: float, hi: float) -> Engine:
    """The steps that lie wholly inside [lo, hi], with what they hold."""
    steps = []
    for root in nest([s for s in spans if s.name in NAMES]):
        if root.name != STEP or root.start < lo or root.end > hi:
            continue
        waits = [(s.start, s.end) for s in root.nested()
                 if s.name.endswith(WAIT)]
        wait_s = trace_reduce.length(
            trace_reduce.union(waits, root.start, root.end))
        steps.append(Step(root, wait_s))
    return Engine(steps)


def load(path: str) -> Tuple[Optional[trace_reduce.Interval], List[Span]]:
    """The harness's sub-window and the engine's spans on the thread
    that holds it; in a trace without the sub-window, None and the
    spans of the first thread that holds any."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    first: List[Span] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            marks, spans = [], []
            for e in line.events:
                start = e.start_ns * 1e-9
                end = (e.start_ns + e.duration_ns) * 1e-9
                if e.name == trace_reduce.TRACED_SPAN:
                    marks.append(trace_reduce.Event(e.name, start, end))
                elif e.name in NAMES:
                    spans.append(Span(e.name, start, end,
                                      {k: v for k, v in e.stats}))
            window = trace_reduce.Trace({}, {}, {line.name: marks}).window()
            if window is not None:
                return window, spans
            first = first or spans
    return None, first


@functools.lru_cache(maxsize=4)
def read(path: str) -> Engine:
    window, spans = load(path)
    if window is None:
        return Engine([])
    return reduce(spans, *window)


def of_run(run) -> Engine:
    """The engine's spans in a traced run of the harness: the trace that
    ``bench/run.py`` wrote for this cell."""
    from bench import run as harness
    directory = pathlib.Path(harness.TRACE_DIR) / run.workload["name"]
    try:
        path = trace_reduce.find_xplane(str(directory))
    except FileNotFoundError:
        return Engine([])
    return read(path)
