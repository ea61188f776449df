"""From a profiler trace to the numbers the per-layer metrics read.

A traced run records one JAX profiler trace (an ``.xplane.pb`` file)
over a sub-window of its measured window.  This module reads it with
``jax.profiler.ProfileData`` and nothing else, and reduces it:

* device events: for every chip, the operations that ran (the
  ``XLA Ops`` line) and the programs they belong to (``XLA Modules``);
* busy time: the union of the intervals in which an operation ran,
  clipped to the sub-window, averaged over chips; idle share is
  1 - busy / window;
* time by operation and by program name, and the number of each;
* collective time left exposed: the part of the union of collective
  operations (all-reduce, all-gather, ...) during which no other
  operation ran on that chip;
* a breakdown for the ledger: the device operations that took most
  time, and the idle gaps grouped by what the host was doing meanwhile
  (the innermost host span on the harness's thread that covers a gap's
  midpoint).

The sub-window is the span of the harness's ``bench.traced`` host
annotation, so host and device times are on the profiler's one clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

TRACED_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|send|recv", re.IGNORECASE)

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float        # seconds on the profiler's clock
    end: float


@dataclasses.dataclass
class Trace:
    """Device operations and programs per chip, and host spans."""
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: Dict[str, List[Event]]        # thread name -> spans

    def window(self) -> Optional[Interval]:
        """The sub-window: the harness's ``bench.traced`` span."""
        for spans in self.host.values():
            for e in spans:
                if e.name == TRACED_SPAN:
                    return (e.start, e.end)
        return None


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = [Event(short_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events]
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(evs)
            elif m and line.name == MODULES_LINE:
                modules.setdefault(int(m.group(1)), []).extend(evs)
            elif plane.name.startswith("/host:"):
                host.setdefault(line.name, []).extend(evs)
    return Trace(ops, modules, host)


# -- interval arithmetic ------------------------------------------------------

def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted
    disjoint intervals."""
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi)
    out: List[Interval] = []
    for a, b in clipped:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        elif b > a:
            out.append((a, b))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clipped(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end, hi) - max(e.start, lo))


# -- reductions ---------------------------------------------------------------

@dataclasses.dataclass
class Summary:
    """A trace reduced over one window [lo, hi]."""
    window_s: float
    chips: int
    busy_s: float                        # mean over chips
    op_s: Dict[str, float]               # summed over chips
    op_n: Dict[str, int]
    module_s: Dict[str, float]
    module_n: Dict[str, int]
    collective_exposed_s: float          # mean over chips
    idle_gaps: List[Tuple[str, float]]
    device_ops: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def time(self, pattern: str, of: str = "op") -> float:
        """Seconds of the operations (or programs) whose name matches
        the regular expression ``pattern``, summed over chips."""
        table = self.op_s if of == "op" else self.module_s
        rx = re.compile(pattern)
        return sum(v for k, v in table.items() if rx.search(k))

    def count(self, pattern: str, of: str = "op") -> int:
        table = self.op_n if of == "op" else self.module_n
        rx = re.compile(pattern)
        return sum(v for k, v in table.items() if rx.search(k))


def short_name(name: str) -> str:
    """An operation as the TPU trace names it is its whole HLO
    instruction (``%fusion.12 = bf16[...] fusion(...)``); keep the
    instruction's own name (``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(events: Sequence[Event]) -> List[Event]:
    """The operations that contain no other: a loop or call on the ops
    line spans the operations it runs, which would count twice."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    inner = [True] * len(evs)
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= evs[stack[-1]].end:
            inner[stack[-1]] = False
        stack.append(i)
    return [e for e, keep in zip(evs, inner) if keep]


def op_family(name: str) -> str:
    """An operation's name without the number XLA appends to each
    instance (``fusion.123`` -> ``fusion``)."""
    return re.sub(r"[.:]\d+$", "", name)


def summarize(trace: Trace, lo: float, hi: float,
              host_thread: Optional[str] = None, top: int = 10) -> Summary:
    chips = sorted(trace.ops)
    if not chips:
        raise ValueError("the trace holds no device operations")
    op_s: Dict[str, float] = {}
    op_n: Dict[str, int] = {}
    busy_total, exposed_total = 0.0, 0.0
    busy_by_chip = {}
    for c in chips:
        evs = leaves([e for e in trace.ops[c] if e.end > lo and e.start < hi])
        for e in evs:
            op_s[e.name] = op_s.get(e.name, 0.0) + _clipped(e, lo, hi)
            op_n[e.name] = op_n.get(e.name, 0) + 1
        busy = union(((e.start, e.end) for e in evs), lo, hi)
        busy_by_chip[c] = busy
        busy_total += length(busy)
        coll = union(((e.start, e.end) for e in evs
                      if COLLECTIVE.search(e.name)), lo, hi)
        other = union(((e.start, e.end) for e in evs
                       if not COLLECTIVE.search(e.name)), lo, hi)
        exposed_total += length(subtract(coll, other))
    module_s: Dict[str, float] = {}
    module_n: Dict[str, int] = {}
    for c in chips:
        for e in trace.modules.get(c, []):
            if e.end > lo and e.start < hi:
                module_s[e.name] = (module_s.get(e.name, 0.0)
                                    + _clipped(e, lo, hi))
                module_n[e.name] = module_n.get(e.name, 0) + 1
    families: Dict[str, float] = {}
    for k, v in op_s.items():
        f = op_family(k)
        families[f] = families.get(f, 0.0) + v / len(chips)
    device_ops = sorted(families.items(), key=lambda kv: -kv[1])[:top]
    gaps = subtract([(lo, hi)], busy_by_chip[chips[0]])
    idle = _attribute(gaps, trace.host.get(host_thread, []) if host_thread
                      else _thread_of(trace, TRACED_SPAN))
    return Summary(hi - lo, len(chips), busy_total / len(chips), op_s, op_n,
                   module_s, module_n, exposed_total / len(chips),
                   sorted(idle.items(), key=lambda kv: -kv[1])[:top],
                   device_ops)


def _thread_of(trace: Trace, span: str) -> List[Event]:
    for spans in trace.host.values():
        if any(e.name == span for e in spans):
            return spans
    return []


def _attribute(gaps: Sequence[Interval], spans: Sequence[Event]
               ) -> Dict[str, float]:
    """Idle seconds by the innermost host span covering each gap's
    midpoint ("(no host span)" where none does).  Spans on one thread
    nest, so the innermost is the latest-starting one still open."""
    spans = sorted((e for e in spans if e.name != TRACED_SPAN),
                   key=lambda e: e.start)
    starts = [e.start for e in spans]
    out: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        name = "(no host span)"
        for k in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[k].end >= mid:
                name = spans[k].name
                break
        out[name] = out.get(name, 0.0) + (b - a)
    return out
