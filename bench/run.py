#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration (``bench/configs/<config>.json``), traffic mix
(``bench/traffic/<traffic>.json``), check limits
(``bench/cells/<cell>.json``) and metric readers
(``bench/metrics/<metric>.py``) are all found by name.

One process, one thread:

1. set-up: weights made on the device from the seed in one program
   (on a mesh, each chip makes its own shards), the serving engine
   built as a user builds it (``make_serve_engine``), every program
   shape the traffic can use warmed by set-up traffic, and for a
   backlog the ramp to full slots in steady state
   (``traffic.steady_start``);
2. the window: ``--seconds`` of traffic (``bench/drive.py``), with
   compilations counted (there should be none); scheduled traffic then
   drains the requests due in the window;
3. with ``--trace 1``, a sub-window of the window is traced and the
   per-layer metrics read from it; otherwise the end-to-end metrics;
4. the check: the engine is freed, a sample of the requests with
   served tokens (finished or still running) runs through the
   configuration's float32 reference
   (``bench/check.py``) and the widest logit gap is held to its limit.

It refuses to run on anything but a TPU, or on fewer chips than the
cell asks for.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
(traced) ``breakdown``, and last ``checks``, each compared number with
its limit; the same numbers are the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench import check, drive, measured, trace_reduce, traffic  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
# the traced sub-window: where it opens in the window, and how long
TRACE_AT, TRACE_SECONDS = 0.25, 5.0


class BenchError(RuntimeError):
    """The run cannot produce a result."""


# -- finding things by name ---------------------------------------------------

def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, bench: Dict) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def cell_files(w: Dict, root: pathlib.Path = ROOT) -> Dict:
    """The configuration, the traffic mix and the check limits of a cell."""
    here = root / "bench"
    return {"config": load_json(here / "configs" / f"{w['config']}.json"),
            "mix": load_json(here / "traffic" / f"{w['traffic']}.json"),
            "cell": load_json(here / "cells" / f"{w['name']}.json")}


def metrics_of(w: Dict, bench: Dict, traced: bool) -> List[Dict]:
    """The metrics a run of this cell reports: end-to-end untraced,
    per-layer traced; each where its ``workloads`` name the cell, or
    everywhere if it has none."""
    table = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in table if w["name"] in m.get("workloads", [w["name"]])]


def reader(name: str, root: pathlib.Path = ROOT):
    """``bench/metrics/<name>.py``'s ``read``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_of(kind: str, root: pathlib.Path = ROOT) -> Dict:
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


# -- the program under test ---------------------------------------------------

HF_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype",
}


def model_config(config: Dict):
    """The program's configuration object for a configuration file: the
    registry's entry for its ``model`` with every ``HF_KEYS`` key of the
    file, the architecture's ``qk_norm`` and ``qkv_bias``, and last any
    field of ``ModelConfig`` that the file's optional ``program`` object
    names.  A key of ``HF_KEYS`` may be left out of the file only where
    the ``program`` object gives its field, so that every departure from
    the published file is written down in it."""
    from repro.configs import get_config
    base = get_config(config["model"])
    program = config.get("program", {})
    unknown = sorted(set(program) - {f.name for f in dataclasses.fields(base)})
    if unknown:
        raise BenchError(f"the configuration's program object names no "
                         f"field of ModelConfig: {unknown}")
    missing = sorted(src for src, dst in HF_KEYS.items()
                     if src not in config and dst not in program)
    if missing:
        raise BenchError(f"the configuration file lacks {missing}, and its "
                         f"program object gives no field for them")
    fields = {dst: config[src] for src, dst in HF_KEYS.items()
              if src in config}
    arch = config.get("architecture", {})
    fields["qk_norm"] = bool(arch.get("qk_norm", False))
    fields["qkv_bias"] = bool(arch.get("qkv_bias", False))
    fields.update(program)
    return dataclasses.replace(base, **fields)


def make_weights(ref, dims: Dict, mcfg, seed: int,
                 mesh_shape: Sequence[int]):
    """The reference's weights from the seed, in one program on the
    device, after checking that the program expects this layout.  On a
    mesh of more than one device every leaf is made under the sharding
    that the serving engine keeps for it on ``serve_mesh(dp, mp)``, so
    each chip creates only its own shard and the engine moves nothing."""
    from repro.models import model as model_lib
    want = {tuple(k.key for k in path): tuple(leaf.shape)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                model_lib.param_specs(mcfg))[0]}
    have = {k: tuple(v) for k, v in ref.shapes(dims).items()}
    if want != have:
        raise BenchError(f"the program's parameter layout differs from the "
                         f"reference's: {sorted(set(want) ^ set(have))}")
    words = traffic.seed_sequence(seed, traffic.WEIGHTS).generate_state(2)
    key = jax.random.wrap_key_data(jax.numpy.asarray(words, jax.numpy.uint32))
    if tuple(mesh_shape) == (1, 1):
        # one device: weights under a one-device mesh's sharding made the
        # engine compile 4-5 more programs in set-up and 1-2 inside the
        # measured window on a TPU v5e chip
        params = jax.jit(lambda k: ref.init(dims, k))(key)
    else:
        from repro.distributed import sharding
        mesh = sharding.serve_mesh(*mesh_shape)
        init = functools.partial(ref.init, dims)
        placed = sharding.param_shardings(jax.eval_shape(init, key), mesh)
        params = jax.jit(init, out_shardings=placed)(key)
    jax.block_until_ready(params)
    return params


class CompileCounter:
    """Counts XLA backend compilations while open."""

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


def peak_bytes(devices) -> Optional[int]:
    """Peak device memory on the fullest device: the peak of arrays plus
    the peak held for the programs' temporaries, which a TPU reserves
    apart and ``peak_bytes_in_use`` leaves out."""
    peaks = [(d.memory_stats() or {}) for d in devices]
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in peaks if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


# -- tracing a sub-window -----------------------------------------------------

class Tracer:
    """Opens the profiler at the first step at least ``at`` seconds into
    the window at which a request waits for its first token (scheduled
    traffic can leave the engine idle for seconds, and a sub-window
    without a prefill holds nothing for the prefill metrics to read)
    and closes it after ``seconds``, at step boundaries; meanwhile each
    step's work is recorded (``drive.WorkProbe``)."""

    def __init__(self, directory: pathlib.Path, at: float, seconds: float,
                 chunk: int) -> None:
        self.dir = directory
        self.at, self.seconds, self.chunk = at, seconds, chunk
        self.on = False
        self.done = False
        self.work: List[drive.StepWork] = []
        self.t0 = None
        self.opened = None
        self._span = None

    def tick(self, client: drive.Client) -> None:
        now = client.clock()
        if self.t0 is None:
            self.t0 = now
        if (not self.on and not self.done and now - self.t0 >= self.at
                and any(not r.stamps for r in client.inflight)):
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, not every call
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(trace_reduce.TRACED_SPAN)
            self._span.__enter__()
            self.on = True
            self.opened = now
        elif self.on and now - self.opened >= self.seconds:
            self.close(client)

    def close(self, client: drive.Client) -> None:
        if self.on:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False
            self.done = True

    def summary(self) -> trace_reduce.Summary:
        if not self.done:
            raise BenchError("the window closed before the traced "
                             "sub-window did")
        tr = trace_reduce.load(trace_reduce.find_xplane(str(self.dir)))
        lo, hi = tr.window()
        return trace_reduce.summarize(tr, lo, hi)


# -- one run ------------------------------------------------------------------

def set_up(config: Dict, seed: int):
    """Weights from the seed, the engine as a user builds it, and every
    program shape warmed by set-up traffic.  Returns (reference, its
    sizes, weights, engine, client)."""
    from repro.runtime.serve_loop import Request, make_serve_engine
    ref = check.reference_module(config)
    dims = ref.dims(config)
    mcfg = model_config(config)
    eng = dict(config["engine"])
    params = make_weights(ref, dims, mcfg, seed, config["mesh"])
    engine = make_serve_engine(mcfg, params, mesh_shape=tuple(config["mesh"]),
                               **eng)
    client = drive.Client(engine, Request, dims["V"], seed)
    drive.warm_up(client, traffic.warmup_waves(
        eng["prefill_chunk"], eng["slots"], eng["decode_horizon"],
        eng["max_len"], eng["block_size"]))
    return ref, dims, params, engine, client


def run_cell(w: Dict, files: Dict, seed: int, seconds: float, traced: bool,
             devices: Sequence, bench: Dict, log=print) -> Dict:
    """Set-up, window, metrics and check of one cell; returns the
    result line's fields."""
    config, mix, cell = files["config"], files["mix"], files["cell"]
    slots, chunk = (config["engine"][k] for k in ("slots", "prefill_chunk"))
    if mix["kind"] == "poisson" and not mix.get("rate_per_s"):
        raise BenchError(f"traffic {w['traffic']!r} has no rate_per_s")

    with CompileCounter() as warm:
        ref, dims, params, engine, client = set_up(config, seed)
        depth = 0
        if mix["kind"] == "backlog":
            depth = int(mix["queue_per_slot"]) * slots
            specs = itertools.chain(
                traffic.steady_start(mix, seed, slots),
                traffic.stream(mix, seed, seconds, rid0=slots))
            drive.ramp(client, specs, slots, depth)
        else:
            specs = traffic.stream(mix, seed, seconds)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s, {warm.count} compilations "
        f"({warm.seconds:.3f} s)")

    tracer = (Tracer(TRACE_DIR / w["name"], TRACE_AT * seconds,
                     min(TRACE_SECONDS, seconds * (1 - TRACE_AT)), chunk)
              if traced else None)
    with CompileCounter() as inside:
        if mix["kind"] == "backlog":
            start, end = drive.backlog_window(client, specs, depth, seconds,
                                              tracer)
            send_end = end
        else:
            start, send_end, end = drive.poisson_window(
                client, specs, seconds, mix["drain_cap_s"], tracer)
    log(f"window {end - start:.3f} s: {inside.count} compilations inside")
    lag = client.send_lag
    log(f"generator lateness: max {1000 * max(lag, default=0.0):.3f} ms, "
        f"p95 {1000 * (drive.percentile(lag, 95) or 0.0):.3f} ms over "
        f"{len(lag)} sends")

    memory = peak_bytes(devices)
    records = [r for rid, r in sorted(client.records.items())
               if rid < traffic.WARMUP_RID0]
    peaks = peaks_of(devices[0].device_kind) if traced else {}
    run = measured.Run(w, config, mix, dims, peaks, len(devices), records,
                       start, send_end, end, setup_s, client.decode_rows)
    if tracer is not None:
        run.work = tracer.work
        run.trace = tracer.summary()

    values = {}
    for m in metrics_of(w, bench, traced):
        v = reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if mix["kind"] == "backlog":
        counted = records
        failed = sum(1 for r in records if r.failed)
    else:
        counted = run.due_in_window()
        failed = sum(1 for r in counted if not r.finished)
    log(f"requests: {len(counted)} counted, {failed} failed; "
        f"{run.tokens_seen(start, end)} tokens seen in the window")
    if mix["kind"] == "poisson" and counted:
        ttft = run.ttft(counted)
        log(f"time to first token over {len(ttft)} requests: mean "
            f"{1000 * sum(ttft) / len(ttft):.3f} ms, median "
            f"{1000 * drive.percentile(ttft, 50):.3f} ms, max "
            f"{1000 * max(ttft):.3f} ms")

    served = [r.req for r in records if r.req.out]
    del engine, client, tracer
    gc.collect()
    reading = check.served_gap(ref, dims, params,
                               check.sample(served, mix["check_requests"],
                                            seed))
    log(f"check: {reading['tokens']} served tokens of "
        f"{reading['requests']} requests against the reference")
    verdict = check.verdict(reading, cell["limits"])

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": verdict["correct"], "attempted": len(counted),
           "failed": failed, "metrics": values, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = verdict["checks"]
    return out


def main(argv: Sequence[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    def log(msg: str) -> None:
        print(f"[{args.workload}] {msg}", file=sys.stderr, flush=True)

    bench = benchmark()
    w = workload(args.workload, bench)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU (JAX found {devices[0].platform!r}); refusing to run")
        return 2
    if len(devices) < w["chips"]:
        log(f"needs {w['chips']} chips, found {len(devices)}")
        return 2
    out = run_cell(w, cell_files(w), args.seed, args.seconds,
                   bool(args.trace), devices[:w["chips"]], bench, log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
