"""The engine's profiler spans (docs/tracing.md): the documented names
and nothing else, every phase and every wait inside a ``serve.step``,
each ``.wait`` inside its own phase, and stats that agree with what
the requests were served.

The engine serves a few requests under ``jax.profiler`` on the CPU
(paged KV, chunks of 16, a decode horizon of 4), and the trace is read
back with ``ProfileData``.  The same checks run on a (1, 2) mesh with
two forced host devices in a subprocess, so the ``jax.set_mesh`` path
is covered while this session keeps its single CPU device."""

import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import model
from repro.runtime.serve_loop import ContinuousBatchingEngine, Request

CFG = ARCHS["qwen3-8b"].reduced()
ENGINE = dict(slots=3, max_len=96, kv_layout="paged", block_size=8,
              prefill_chunk=16, decode_horizon=4)
PROMPTS = (37, 9, 20, 45)
OUTPUTS = (9, 6, 11, 7)

PHASES = ("serve.step", "serve.admit", "serve.place", "serve.prefill_chunk",
          "serve.first_token", "serve.reserve", "serve.masks",
          "serve.decode", "serve.verify", "serve.replay")
# what this configuration runs: no speculation, so no verify; placement,
# chunks, first tokens and decode calls block on the device
EMITTED = (set(PHASES) - {"serve.verify"}) | {
    "serve.place.wait", "serve.prefill_chunk.wait",
    "serve.first_token.wait", "serve.decode.wait"}


def make_requests():
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, CFG.vocab_size, p)
                    .astype(np.int32), max_new_tokens=m)
            for i, (p, m) in enumerate(zip(PROMPTS, OUTPUTS))]


def serve_traced(engine, reqs, trace_dir):
    """Serve ``reqs`` to the end under a profiler trace; returns, per
    step, the requests that gained a decoded token (a request's first
    token comes from its prefill)."""
    for r in reqs:
        engine.submit(r)
    decoded = lambda: [max(len(r.out) - 1, 0) for r in reqs]
    rows = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        while engine.queue or engine.num_active:
            before = decoded()
            engine.step()
            rows.append(sum(a > b for a, b in zip(decoded(), before)))
    finally:
        jax.profiler.stop_trace()
    return rows


def read_spans(trace_dir):
    """Every ``serve.*`` span in the trace as (name, start, end, stats)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def inside(a, b):
    return b[1] <= a[1] and a[2] <= b[2]


def check_spans(spans, prompts, outputs, rows):
    names = {s[0] for s in spans}
    assert names == EMITTED, names ^ EMITTED
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == len(rows)
    for s in spans:
        if s[0] != "serve.step":
            assert any(inside(s, st) for st in steps), s
        if s[0].endswith(".wait"):
            phase = s[0][:-len(".wait")]
            assert any(p[0] == phase and inside(s, p) for p in spans), s
    # the stats agree with what the requests were served
    chunked = {}
    for name, _, _, st in spans:
        if name == "serve.prefill_chunk":
            assert st["tokens"] <= st["padded"]
            chunked[st["rid"]] = chunked.get(st["rid"], 0) + st["tokens"]
    assert chunked == dict(enumerate(prompts))
    firsts = [st["rid"] for name, _, _, st in spans
              if name == "serve.first_token"]
    assert sorted(firsts) == list(range(len(prompts)))
    replayed = sum(st["tokens"] for name, _, _, st in spans
                   if name == "serve.replay")
    assert replayed == sum(n - 1 for n in outputs)
    for step, want in zip(steps, rows):
        calls = [st for name, a, b, st in spans
                 if name == "serve.decode" and inside((name, a, b), step)]
        assert sum(st["rows"] for st in calls) == want
        for st in calls:
            assert st["horizon"] in (1, 4)
            assert st["keys"] >= st["rows"] and st["pages"] >= st["rows"]


@pytest.fixture(scope="module")
def params():
    return model.init_params(CFG, jax.random.PRNGKey(0))


def test_spans_name_every_phase_and_wait(params, tmp_path):
    reqs = make_requests()
    engine = ContinuousBatchingEngine(CFG, params, **ENGINE)
    rows = serve_traced(engine, reqs, tmp_path)
    assert [len(r.out) for r in reqs] == list(OUTPUTS)
    check_spans(read_spans(tmp_path), PROMPTS, OUTPUTS, rows)


def test_tokens_are_the_same_with_and_without_a_trace(params, tmp_path):
    traced = make_requests()
    serve_traced(ContinuousBatchingEngine(CFG, params, **ENGINE), traced,
                 tmp_path)
    plain = make_requests()
    engine = ContinuousBatchingEngine(CFG, params, **ENGINE)
    for r in plain:
        engine.submit(r)
    engine.run()
    assert [r.out for r in traced] == [r.out for r in plain]


def test_walked_counts_each_slots_live_pages(params, tmp_path):
    """``serve.decode``'s ``walked`` is the pages the paged decode kernel
    copies for the call's first token: every slot's pages up to its
    device length (capped at the table), free and frozen slots too."""
    engine = ContinuousBatchingEngine(CFG, params, **ENGINE)
    lengths = []
    stats = engine._decode_stats

    def read_lengths_first(rows, horizon):
        lengths.append(np.asarray(engine.cache["length"]))
        return stats(rows, horizon)

    engine._decode_stats = read_lengths_first
    serve_traced(engine, make_requests(), tmp_path)
    walked = [st["walked"] for name, _, _, st in read_spans(tmp_path)
              if name == "serve.decode"]
    bs = ENGINE["block_size"]
    nb = ENGINE["max_len"] // bs
    assert len(walked) == len(lengths) > 0
    assert walked == [int((np.minimum(n // bs, nb - 1) + 1).sum())
                      for n in lengths]
    assert max(walked) < ENGINE["slots"] * nb


_MESH = r"""
import json, sys
sys.path.insert(0, "tests")
import jax
from repro.models import model
from repro.runtime.serve_loop import make_serve_engine
import test_serve_spans as t

assert len(jax.devices()) == 2
params = model.init_params(t.CFG, jax.random.PRNGKey(0))
engine = make_serve_engine(t.CFG, params, mesh_shape=(1, 2), **t.ENGINE)
reqs = t.make_requests()
rows = t.serve_traced(engine, reqs, sys.argv[1])
print(json.dumps({"rows": rows, "out": [len(r.out) for r in reqs]}))
"""


def test_spans_on_a_tensor_sharded_mesh(tmp_path):
    root = os.path.join(os.path.dirname(__file__), "..")
    p = subprocess.run(
        [sys.executable, "-c", _MESH, str(tmp_path)], capture_output=True,
        text=True, timeout=600, cwd=root,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["out"] == list(OUTPUTS)
    check_spans(read_spans(tmp_path), PROMPTS, OUTPUTS, got["rows"])
