"""The benchmark's harness on the CPU: cells, configurations, traffic
and metric readers found by name, names and units within the contract,
traffic made from the seed alone, metrics over the whole window, and
the refusal to run without a TPU."""

import dataclasses
import hashlib
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import check, drive, measured, run, traffic
from repro.configs import get_config

ROOT = run.ROOT
BENCH = run.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_benchmark_file_has_exactly_the_contract_keys():
    assert set(BENCH) == KEYS
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_by_name(w):
    files = run.cell_files(w)
    assert files["config"]["name"] == w["config"]
    assert files["mix"]["kind"] in ("backlog", "poisson")
    assert files["cell"]["limits"]
    cfg = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert (ROOT / cfg["file"]).is_file()
    assert set(cfg["reduced"]) <= set(files["config"]["reduced"])
    for traced in (False, True):
        assert run.metrics_of(w, BENCH, traced)


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    assert callable(run.reader(m["name"]))
    for name in m.get("workloads", []):
        run.workload(name, BENCH)


def test_names_and_units_are_legal():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [w[k] for w in BENCH["workloads"] for k in ("config", "traffic")]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[group]}) == len(BENCH[group])
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w])


# the files written before a configuration could carry a ``program``
# object (a later file may, and may then leave out the keys it gives)
@pytest.mark.parametrize("name", ["qwen3-8b", "qwen2-7b", "qwen3-8b-tp4"])
def test_configuration_files_map_as_every_key_was_required(name):
    """With every ``HF_KEYS`` key in the file and no ``program`` object,
    the mapping is the one that required every key."""
    cfg = run.load_json(ROOT / "bench" / "configs" / f"{name}.json")
    arch = cfg["architecture"]
    want = dataclasses.replace(
        get_config(cfg["model"]),
        **{dst: cfg[src] for src, dst in run.HF_KEYS.items()},
        qk_norm=bool(arch["qk_norm"]), qkv_bias=bool(arch["qkv_bias"]))
    assert "program" not in cfg
    assert run.model_config(cfg) == want


def _qwen3():
    return run.load_json(ROOT / "bench" / "configs" / "qwen3-8b.json")


def test_a_program_object_sets_a_field_of_the_program_config():
    cfg = _qwen3()
    cfg["program"] = {"window": 1024, "rope_theta": 5e4}
    got = run.model_config(cfg)
    assert got.window == 1024 and got.rope_theta == 5e4
    assert dataclasses.replace(got, window=None,
                               rope_theta=1e6) == run.model_config(_qwen3())


def test_a_program_object_naming_no_field_is_refused():
    cfg = _qwen3()
    cfg["program"] = {"kv_lora_rank": 512}
    with pytest.raises(run.BenchError, match="kv_lora_rank"):
        run.model_config(cfg)


def test_a_file_without_head_dim_maps_when_the_program_object_gives_it():
    cfg = _qwen3()
    del cfg["head_dim"]
    with pytest.raises(run.BenchError, match="head_dim"):
        run.model_config(cfg)
    cfg["program"] = {"head_dim": 192}
    got = run.model_config(cfg)
    assert got.head_dim == 192
    assert dataclasses.replace(got, head_dim=128) == run.model_config(_qwen3())


@pytest.mark.parametrize("key", sorted(run.HF_KEYS))
def test_a_key_left_out_without_its_program_field_is_refused(key):
    """A published key missing from the file never falls back to the
    registry's value unnoticed: the program object has to name its
    field, and another field there does not stand in for it."""
    cfg = _qwen3()
    del cfg[key]
    cfg["program"] = {"window": 1024}
    with pytest.raises(run.BenchError, match=key):
        run.model_config(cfg)
    cfg["program"][run.HF_KEYS[key]] = _qwen3()[key]
    assert run.model_config(cfg) == dataclasses.replace(
        run.model_config(_qwen3()), window=1024)


# sha256 of every leaf's path, dtype and bytes, made by make_weights from
# seed 2**33 + 5 at smoke size on one device, as the parent of the mesh
# path made them
ONE_DEVICE_WEIGHTS = {
    "qwen3-8b": "efbc9aab3eb561e2c6cf687dc8e23d307af37f7120f51246a52a86289457323d",
    "qwen2-7b": "6940e4a6a05f1b9b6dfc3ebb448a435e45ec1d921467d7120386458ae40796e9",
}


@pytest.mark.parametrize("name", sorted(ONE_DEVICE_WEIGHTS))
def test_one_device_weights_are_unchanged(name):
    cfg = run.load_json(ROOT / "bench" / "configs" / f"{name}.json")
    assert cfg["mesh"] == [1, 1]
    cfg.update({"hidden_size": 128, "intermediate_size": 256,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 32, "num_hidden_layers": 2, "vocab_size": 512})
    ref = check.reference_module(cfg)
    params = run.make_weights(ref, ref.dims(cfg), run.model_config(cfg),
                              2 ** 33 + 5, cfg["mesh"])
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        # not under a one-device mesh: that made the engine compile
        # again inside the measured window on the chip
        assert isinstance(x.sharding, jax.sharding.SingleDeviceSharding)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(x.dtype).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == ONE_DEVICE_WEIGHTS[name]


class _StatsDevice:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_the_peak_counts_the_memory_reserved_for_programs():
    # a TPU holds a program's temporaries as reserved bytes, apart from
    # the arrays that peak_bytes_in_use counts
    fullest = {"peak_bytes_in_use": 8 << 30, "peak_bytes_reserved": 5 << 30}
    devices = [_StatsDevice({"peak_bytes_in_use": 9 << 30}),
               _StatsDevice(fullest), _StatsDevice(None)]
    assert run.peak_bytes(devices) == 13 << 30
    assert run.peak_bytes([_StatsDevice(None), _StatsDevice({})]) is None


@pytest.mark.parametrize("mix", ["decode-backlog", "longprompt-chat"])
def test_traffic_follows_the_seed_alone(mix):
    spec = run.load_json(ROOT / "bench" / "traffic" / f"{mix}.json")

    def take(seed, n=50):
        s = traffic.stream(spec, seed, 40.0)
        return [dataclasses.astuple(next(s)) for _ in range(n)]

    big = 2 ** 31 + 12345
    assert take(big) == take(big)
    assert take(big) != take(big + 1)
    assert (traffic.prompt_tokens(big, 3, 9, 1000).tolist()
            == traffic.prompt_tokens(big, 3, 9, 1000).tolist())
    # every seed sends the same lengths in one cycle, in its own order
    n = traffic.cycle_size(spec, 40.0)
    for k in (1, 2):                      # prompt, output lengths
        lengths = lambda seed: sorted(x[k] for x in take(seed, n))
        assert lengths(1) == lengths(big)


def test_poisson_window_holds_its_rate():
    spec = {"kind": "poisson", "rate_per_s": 2.0,
            "prompt": {"median": 100, "sigma": 0.5, "min": 10, "max": 400},
            "output": {"median": 10, "sigma": 0.5, "min": 2, "max": 40}}
    s = traffic.stream(spec, 5, 30.0)
    due = [next(s).due for _ in range(120)]
    assert sum(1 for d in due if d < 30.0) == 60
    assert due == sorted(due)


def test_a_backlog_window_opens_in_steady_state():
    spec = run.load_json(ROOT / "bench" / "traffic" / "decode-backlog.json")
    big = 2 ** 31 + 77
    fill = traffic.steady_start(spec, big, 24)
    assert fill == traffic.steady_start(spec, big, 24)
    assert [s.rid for s in fill] == list(range(24))
    prompts = traffic.lognormal_quantiles(spec["prompt"], 24)
    totals = traffic.size_biased_quantiles(spec["output"], 24)
    # every seed fills the slots with one set of lengths, in its own order
    other = traffic.steady_start(spec, big + 1, 24)
    assert other != fill
    assert (sum(s.prompt_len + s.output_len for s in other)
            == sum(s.prompt_len + s.output_len for s in fill))
    # the outputs a slot holds are longer than the mix's own (a long
    # request holds its slot longer), and part of each is already served
    assert sorted(totals) != sorted(traffic.lognormal_quantiles(
        spec["output"], 24))
    assert sum(totals) > 1.3 * sum(traffic.lognormal_quantiles(
        spec["output"], 24))
    assert all(s.output_len >= 1 for s in fill)
    assert all(s.prompt_len + s.output_len
               <= spec["prompt"]["max"] + spec["output"]["max"] for s in fill)
    assert sum(s.prompt_len + s.output_len for s in fill) == (
        sum(prompts) + sum(totals))
    assert sum(s.prompt_len for s in fill) > sum(prompts) + 0.3 * sum(totals)
    # the stream after it goes on from the next number
    after = traffic.stream(spec, big, 51.0, rid0=24)
    assert next(after).rid == 24


def test_the_check_samples_from_the_seed_with_the_longest_context_in():
    @dataclasses.dataclass
    class Served:
        rid: int
        prompt: list
        out: list

    # rid 3 has the most served tokens, rid 7 (still running) reaches
    # furthest: prompt and served tokens together
    reqs = [Served(i, [0] * 10, [1] * (5 + i % 4)) for i in range(20)]
    reqs[7] = Served(7, [0] * 40, [1] * 2)
    reqs[3] = Served(3, [0] * 10, [1] * 25)
    big = 2 ** 31 + 9
    picked = check.sample(reqs, 4, big)
    assert picked[0].rid == 7 and len(picked) == 4
    assert len({r.rid for r in picked}) == 4
    assert [r.rid for r in picked] == [r.rid for r in check.sample(
        list(reversed(reqs)), 4, big)]
    assert check.sample([], 4, big) == []


def test_warmup_reaches_every_prefill_bucket_and_splice_count():
    waves = traffic.warmup_waves(512, 24, 4, 3072, 16)
    assert {w[0].prompt_len for w in waves} == {16, 32, 64, 128, 256, 512}
    assert {len(w) for w in waves} == {1, 4, 8, 16, 24}
    assert all(s.output_len == 7 for w in waves for s in w)


# -- metrics over the whole window, with a fake engine and a fake clock -------

@dataclasses.dataclass
class FakeRequest:
    rid: int
    prompt: object
    max_new_tokens: int
    out: list = dataclasses.field(default_factory=list)
    status: str = "queued"
    submit_t: float = 0.0
    queue_wait_s: float = 0.0
    admit_step: int = -1


class FakeEngine:
    """Serves ``slots`` requests at a time, one token each per step of
    ``step_s`` seconds; every ``stall_every``-th step takes ``stall_s``
    more."""

    def __init__(self, clock, slots=4, step_s=0.05, stall_every=0,
                 stall_s=0.0):
        self.clock, self.slots, self.step_s = clock, slots, step_s
        self.stall_every, self.stall_s = stall_every, stall_s
        self.queue, self.running, self.n = [], [], 0

    def submit(self, req):
        req.submit_t = self.clock.now
        self.queue.append(req)

    def step(self):
        while self.queue and len(self.running) < self.slots:
            r = self.queue.pop(0)
            r.status, r.admit_step = "running", self.n
            r.queue_wait_s = self.clock.now - r.submit_t
            self.running.append(r)
        stalled = self.stall_every and self.n % self.stall_every == 0
        self.clock.now += self.step_s + (self.stall_s if stalled else 0.0)
        self.n += 1
        for r in self.running:
            r.out.append(1)
            if len(r.out) >= r.max_new_tokens:
                r.status = "done"
        self.running = [r for r in self.running if r.status != "done"]
        return True


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _backlog_run(stall_s):
    clock = FakeClock()
    engine = FakeEngine(clock, stall_every=10, stall_s=stall_s)
    client = drive.Client(engine, FakeRequest, 100, 1, clock=clock)
    mix = {"kind": "backlog", "queue_per_slot": 2, "cycle": 16,
           "prompt": {"median": 20, "sigma": 0.5, "min": 4, "max": 40},
           "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 30}}
    specs = traffic.stream(mix, 3, 10.0)
    drive.ramp(client, specs, 4, 8)
    start, end = drive.backlog_window(client, specs, 8, 10.0)
    recs = list(client.records.values())
    r = measured.Run({}, {}, mix, {}, {}, 1, recs, start, end, end, 1.0,
                     client.decode_rows)
    return {m: run.reader(m)(r) for m in ("output_tok_s", "itl_p95_ms")}, r


def test_a_planted_stall_moves_the_rate_and_the_tail():
    clean, r = _backlog_run(0.0)
    stalled, _ = _backlog_run(0.2)
    assert clean["output_tok_s"] == pytest.approx(4 / 0.05, rel=0.02)
    assert clean["itl_p95_ms"] == pytest.approx(50.0)
    # one step in ten stalls: a tenth of all gaps, so the rate over the
    # whole window falls by the lost time and the tail over every gap
    # reads the stall
    assert stalled["output_tok_s"] == pytest.approx(4 / 0.07, rel=0.02)
    assert stalled["itl_p95_ms"] == pytest.approx(250.0)
    # a request's first token comes from prefill, not decode
    assert 3.0 < run.reader("decode_occupancy.batch")(r) < 4.0


def test_the_tail_counts_every_request():
    clock = FakeClock()
    engine = FakeEngine(clock, slots=2, step_s=0.1)
    client = drive.Client(engine, FakeRequest, 100, 1, clock=clock)
    mix = {"kind": "poisson", "rate_per_s": 4.0, "drain_cap_s": 30.0,
           "prompt": {"median": 20, "sigma": 0.5, "min": 4, "max": 40},
           "output": {"median": 10, "sigma": 0.1, "min": 10, "max": 10}}
    specs = traffic.stream(mix, 3, 5.0)
    start, send_end, end = drive.poisson_window(client, specs, 5.0, 30.0)
    recs = list(client.records.values())
    r = measured.Run({}, {}, mix, {}, {}, 1, recs, start, send_end, end, 1.0)
    due = r.due_in_window()
    assert len(due) == 20
    assert all(x.finished for x in due)
    # 4 requests/s against 2 slots x 10 tokens x 0.1 s: the queue grows
    # through the window, and requests drained after it still count
    assert sum(r.ttft(due)) / len(due) > 1.0
    assert max(x.stamps[0] for x in due) > send_end


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", w,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refusing" in p.stderr


def test_the_traced_sub_window_opens_only_with_a_prefill_due(
        monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(run.jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(run.jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    clock = FakeClock()
    client = drive.Client(FakeEngine(clock), FakeRequest, 100, 1, clock=clock)
    tracer = run.Tracer(tmp_path / "trace", 1.0, 2.0, 16)
    tracer.tick(client)                   # the window opens
    clock.now = 1.5
    tracer.tick(client)                   # late enough, but nothing to run
    decoding = drive.Record(None, None, 0.0, 0.0, stamps=[1.0])
    client.inflight.append(decoding)
    clock.now = 2.0
    tracer.tick(client)                   # only a request past its prefill
    assert not tracer.on and calls == []
    client.inflight.append(drive.Record(None, None, 2.5, 2.5))
    clock.now = 3.0
    tracer.tick(client)
    assert tracer.on and tracer.opened == 3.0
    clock.now = 4.0
    tracer.tick(client)
    assert tracer.on
    clock.now = 5.0
    tracer.tick(client)
    assert tracer.done and not tracer.on and calls == ["start", "stop"]
