"""Request streams made from a traffic file and a seed.

One general generator reads every mix under ``bench/traffic/``.  A file
gives the kind of arrivals and the length distributions; nothing here
knows a mix by name.

* ``backlog``: an offline batch.  Requests have no schedule; the harness
  keeps ``queue_per_slot`` x slots of them waiting, so every slot is
  always busy.
* ``poisson``: an open loop at ``rate_per_s``.  Requests are due at
  their arrival time whether or not earlier ones have finished.

Lengths are lognormal (``median``, ``sigma``), clipped to
``[min, max]``.  Every seed gets the same set of lengths and gaps: a
cycle of ``n`` requests takes the ``n`` quantiles ``(i + 0.5) / n`` of
each distribution and shuffles them.  The seed picks the order, so it
changes which request comes when and not how much work a window holds.

A backlog's window opens in steady state (:func:`steady_start`): the
requests that fill the slots when it opens are already part-way
through their outputs, as in a batch that has been running for a while.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, Iterator, List, Optional

import numpy as np

# streams drawn from one --seed; each use has its own
WEIGHTS, ORDER, TOKENS, SAMPLE = range(4)
# set-up requests are numbered apart from the measured ones
WARMUP_RID0 = 10 ** 9


def seed_sequence(seed: int, *stream: int) -> np.random.SeedSequence:
    """Entropy for one use of ``seed`` (any whole number, 64 bits or
    more: ``SeedSequence`` takes the integer whole)."""
    return np.random.SeedSequence([abs(int(seed)), *stream])


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_sequence(seed, *stream))


@dataclasses.dataclass
class Spec:
    """One request before it is sent: ``due`` is seconds after the
    window opens (None for a backlog, whose requests are due when the
    harness sends them)."""
    rid: int
    prompt_len: int
    output_len: int
    due: Optional[float] = None


def lognormal_quantiles(dist: Dict, n: int) -> List[int]:
    """The ``n`` midpoint quantiles of a clipped lognormal, as ints."""
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        x = math.exp(mu + sigma * z)
        out.append(int(round(min(max(x, dist["min"]), dist["max"]))))
    return out


def exponential_quantiles(n: int) -> List[float]:
    """The ``n`` midpoint quantiles of a unit-mean exponential."""
    return [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]


def cycle_size(mix: Dict, seconds: float) -> int:
    """Requests per shuffled cycle: a backlog's file sets it; a Poisson
    mix sends ``rate x seconds`` per window, one cycle per window."""
    if mix["kind"] == "backlog":
        return int(mix["cycle"])
    if mix["kind"] == "poisson":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def stream(mix: Dict, seed: int, seconds: float,
           rid0: int = 0) -> Iterator[Spec]:
    """Requests in the order they are sent, without end, numbered from
    ``rid0``.

    A Poisson cycle spans exactly ``seconds``: its ``n`` gaps (the unit
    exponential's quantiles, in the seed's order) are scaled to sum to
    it, so the window holds exactly ``n`` arrivals and the next cycle
    continues the same rate into the drain."""
    n = cycle_size(mix, seconds)
    prompts = lognormal_quantiles(mix["prompt"], n)
    outputs = lognormal_quantiles(mix["output"], n)
    gaps = exponential_quantiles(n)
    scale = seconds / sum(gaps)
    order = rng(seed, ORDER)
    rid, t0 = rid0, 0.0
    while True:
        p = order.permutation(n)
        o = order.permutation(n)
        g = order.permutation(n)
        t = t0
        for k in range(n):
            due = None
            if mix["kind"] == "poisson":
                due = t
                t += gaps[g[k]] * scale
            yield Spec(rid, prompts[p[k]], outputs[o[k]], due)
            rid += 1
        t0 += seconds


def size_biased_quantiles(dist: Dict, n: int, grid: int = 4096) -> List[int]:
    """The ``n`` midpoint quantiles of a clipped lognormal weighted by
    its own value: the total length of the request a slot holds at a
    random moment of a batch in steady state (a request holds its slot
    for as long as it is)."""
    xs = lognormal_quantiles(dist, grid)          # ascending
    cum = np.cumsum(np.asarray(xs, np.float64))
    cum /= cum[-1]
    return [xs[int(np.searchsorted(cum, (i + 0.5) / n))] for i in range(n)]


def steady_start(mix: Dict, seed: int, slots: int) -> List[Spec]:
    """The requests that fill a backlog's slots when its window opens,
    numbered ``0 .. slots - 1``: each is part-way through its output.

    Their output lengths are the size-biased quantiles of the mix's
    output distribution, and the share ``u`` already served takes the
    quantiles ``(i + 0.5) / slots`` of the uniform, both in the seed's
    order.  A request ``u`` of the way through an output of ``O`` tokens
    is sent with ``floor(u O)`` more prompt tokens (set-up prefills
    them, as the batch once decoded them) and ``O - floor(u O)`` tokens
    still to serve, so the slots start the window at staggered contexts
    and finish at staggered times."""
    prompts = lognormal_quantiles(mix["prompt"], slots)
    outputs = size_biased_quantiles(mix["output"], slots)
    order = rng(seed, ORDER, 1)
    p, o, u = (order.permutation(slots) for _ in range(3))
    out = []
    for k in range(slots):
        total = outputs[o[k]]
        done = min(total - 1, int((u[k] + 0.5) / slots * total))
        out.append(Spec(k, prompts[p[k]] + done, total - done))
    return out


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """The token ids of one prompt: uniform over the vocabulary."""
    return rng(seed, TOKENS, rid).integers(0, vocab, length).astype(np.int32)


def pad_buckets(chunk: int, max_len: int, minimum: int = 16) -> List[int]:
    """Every padded shape a prefill chunk of at most ``chunk`` tokens
    can take: powers of two from ``minimum`` up to the chunk, capped at
    ``max_len`` (the engine pads a chunk's tokens this way)."""
    out, b = [], minimum
    while b < chunk:
        out.append(min(b, max_len))
        b *= 2
    out.append(min(b, max_len))
    return sorted(set(out))


def warmup_waves(chunk: int, slots: int, horizon: int, max_len: int,
                 block_size: int) -> List[List[Spec]]:
    """Set-up traffic that reaches every program shape the window can use.

    Each wave sends ``n`` equal requests at once, so all ``n`` are
    prefilled in one step and cross a page boundary together:

    * prompt lengths: one per padded prefill bucket (each a multiple of
      the page size, so the first decode call needs a fresh page);
    * wave sizes 1, 4, 8, ... up to the slot count: one page splice,
      and every padded count of batched splices;
    * ``horizon + 3`` new tokens: one fused horizon call, then the
      single-step calls a ragged tail takes.
    """
    sizes, n = [1], 4
    while n < slots:
        sizes.append(n)
        n *= 2
    sizes.append(slots)
    lengths = [max(b, block_size) for b in pad_buckets(chunk, max_len)]
    waves, rid = [], WARMUP_RID0
    for k in range(max(len(lengths), len(sizes))):
        plen = lengths[k % len(lengths)]
        wave = []
        for _ in range(sizes[k % len(sizes)]):
            wave.append(Spec(rid, plen, horizon + 3))
            rid += 1
        waves.append(wave)
    return waves
