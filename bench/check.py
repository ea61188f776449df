"""Whether what the timed path served is correct.

Once the window has closed and the engine is freed, a sample of the
requests it served tokens to, finished or still running (drawn from the
seed, the one with the longest context always in it), runs through the configuration's plain reference
once each: prompt and served tokens together, in float32.  Served token
``j`` of a request with ``P`` prompt tokens is the program's greedy pick
from position ``P - 1 + j``; there the reference scores it.  The number
compared is the widest gap, over every sampled token, by which the
reference's logit of the served token lies below the reference's best
logit.  A right token scores 0 or a rounding-sized gap at a near-tie; a
token from a broken cache, kernel or position scores like a random
vocabulary entry, several units down.

On a mesh the weights stay sharded as the serving engine keeps them:
XLA partitions each of the reference's jitted pieces (a layer, the
head) over the weights' shardings, adding the partial sums of the split
matrix products, so no chip ever holds the whole model.

The control (:func:`control_gap`, run by ``bench/calibrate.py`` and the
tests, never by a benchmark run) puts the reference itself in the
program's place, computed a precision below the configuration's, and
reads the same gap for the token it would have picked.
"""

from __future__ import annotations

import importlib
from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

from bench import traffic


def reference_module(cfg: Dict):
    """The configuration's plain reference, ``bench/references/<name>.py``."""
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def sample(served: Sequence, n: int, seed: int) -> List:
    """Up to ``n`` requests with served tokens: the one whose prompt and
    served tokens reach furthest, and the rest drawn from the seed."""
    if not served:
        return []
    pool = sorted(served, key=lambda r: r.rid)
    longest = max(pool, key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    rest = [r for r in pool if r is not longest]
    pick = traffic.rng(seed, traffic.SAMPLE).permutation(len(rest))
    return [longest] + [rest[i] for i in pick[:max(0, n - 1)]]


# sequences are right-padded to a multiple of PAD tokens (few shapes to
# compile; causal attention keeps the padding out of every real row) and
# the reference attends Q_BLOCK query rows at a time
PAD, Q_BLOCK = 1024, 512


def _padded(req) -> np.ndarray:
    seq = np.concatenate([np.asarray(req.prompt, np.int32),
                          np.asarray(req.out, np.int32)])
    toks = np.zeros((-(-len(seq) // PAD) * PAD,), np.int32)
    toks[:len(seq)] = seq
    return toks


def _served_positions(req) -> np.ndarray:
    P = len(req.prompt)
    return np.arange(P - 1, P - 1 + len(req.out))


def served_gap(ref, c: Dict, params, reqs: Sequence) -> Dict:
    """The widest gap of any served token in ``reqs`` (see module doc)."""
    worst, tokens = 0.0, 0
    for r in reqs:
        h = ref.hidden(params, c, jnp.asarray(_padded(r)), "f32",
                       q_block=Q_BLOCK)
        g = np.asarray(ref.gaps(params, h, _served_positions(r), r.out))
        worst = max(worst, float(g.max()))
        tokens += len(g)
    return {"max_logit_gap": worst, "tokens": tokens, "requests": len(reqs)}


def control_gap(ref, c: Dict, params, reqs: Sequence) -> Dict:
    """The same number for the reference computed in float8: at each
    served position of the same prompts and tokens, the gap of the token
    that the float8 pass puts first."""
    worst, tokens = 0.0, 0
    for r in reqs:
        toks = jnp.asarray(_padded(r))
        pos = _served_positions(r)
        first = ref.picks(params, ref.hidden(params, c, toks, "fp8",
                                             q_block=Q_BLOCK), pos, "fp8")
        h = ref.hidden(params, c, toks, "f32", q_block=Q_BLOCK)
        g = np.asarray(ref.gaps(params, h, pos, first))
        worst = max(worst, float(g.max()))
        tokens += len(g)
    return {"max_logit_gap": worst, "tokens": tokens, "requests": len(reqs)}


def verdict(reading: Dict, limits: Dict) -> Dict:
    """Each compared number beside its limit, and whether all hold."""
    checks = {name: {"value": reading[name], "limit": limit}
              for name, limit in limits.items()}
    ok = reading["tokens"] > 0 and all(
        v["value"] <= v["limit"] for v in checks.values())
    return {"correct": bool(ok), "checks": checks}
