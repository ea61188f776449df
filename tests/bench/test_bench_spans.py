"""The engine's spans as the benchmark reads them (``bench/spans.py``):
self time on hand-made nested spans, the three readers on a trace
recorded here on the CPU through the harness's own client and tracer,
and None from every reader where the trace holds no engine span."""

import pathlib
import shutil

import jax
import pytest

from bench import drive, measured, run, spans, traffic

READERS = ("host_ms_per_step.batch", "host_ms_per_step.chat",
           "decode_rows.batch")
FIXTURE = (pathlib.Path(__file__).parent / "data"
           / "decode_backlog_v5e.xplane.pb")


def S(name, start, end, **stats):
    return spans.Span(name, float(start), float(end), stats)


def test_self_time_of_nested_spans_with_overlapping_waits():
    events = [
        S("serve.step", 0, 10, decode_steps=0),
        S("serve.admit", 0.5, 1.5),
        S("serve.place", 0.6, 1.2, rid=3),
        S("serve.place.wait", 0.7, 0.9),
        S("serve.decode", 2, 8, rows=5),
        S("serve.decode.wait", 3, 6),
        S("serve.decode.wait", 5, 7),          # overlaps the first
        S("np.asarray(jax.Array)", 3.5, 4),    # not the engine's: ignored
        S("serve.replay", 8.5, 9, tokens=20),
        # an empty engine's step: counted as a step, not in host time
        S("serve.step", 10.2, 10.7, queued=0, decoding=0, prefilling=0),
    ]
    eng = spans.reduce(events, -1.0, 11.0)
    step, idle = eng.steps
    assert idle.self_s == pytest.approx(0.5)
    assert step.wait_s == pytest.approx(0.2 + 4.0)
    assert step.self_s == pytest.approx(10.0 - 4.2)
    own = eng.phase_self_s()
    assert own["serve.step"] == pytest.approx(10 - 1.0 - 6.0 - 0.5 + 0.5)
    assert own["serve.admit"] == pytest.approx(1.0 - 0.6)
    assert own["serve.place"] == pytest.approx(0.6 - 0.2)
    assert own["serve.decode"] == pytest.approx(6.0 - 4.0)
    assert own["serve.replay"] == pytest.approx(0.5)
    assert "np.asarray(jax.Array)" not in own
    assert eng.host_ms_per_step() == pytest.approx(5800.0)
    assert eng.mean_stat("serve.decode", "rows") == 5
    assert eng.spans("serve.place")[0].stats == {"rid": 3}


def test_a_step_cut_by_the_window_edge_is_left_out():
    events = [
        S("serve.step", 0, 4), S("serve.decode", 1, 3, rows=2),
        S("serve.decode.wait", 1.5, 2.5),
        S("serve.step", 4, 8), S("serve.decode", 5, 7, rows=6),
        S("serve.step", 8, 12), S("serve.decode", 9, 11, rows=9),
    ]
    eng = spans.reduce(events, 3.0, 10.0)
    assert [st.span.start for st in eng.steps] == [4.0]
    assert eng.mean_stat("serve.decode", "rows") == 6
    assert eng.host_ms_per_step() == pytest.approx(4000.0)
    assert spans.reduce(events, 0.0, 12.0).host_ms_per_step() == (
        pytest.approx(1000.0 * (3.0 + 4.0 + 4.0) / 3))
    assert spans.reduce(events, 20.0, 30.0).host_ms_per_step() is None


def _run_for(name):
    return measured.Run({"name": name}, {}, {"kind": "backlog"}, {}, {}, 1,
                        [], 0.0, 0.0, 0.0, 0.0)


@pytest.fixture
def trace_dir(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path)
    return tmp_path


def test_every_reader_is_none_without_engine_spans(trace_dir):
    name = "qwen3-8b.decode-backlog"
    for m in READERS:
        assert run.reader(m)(_run_for(name)) is None      # no trace at all
    (trace_dir / name).mkdir()
    shutil.copy(FIXTURE, trace_dir / name / "chip.xplane.pb")
    for m in READERS:
        assert run.reader(m)(_run_for(name)) is None


def test_readers_on_a_trace_recorded_through_the_harness(trace_dir):
    from repro.configs import ARCHS
    from repro.models import model
    from repro.runtime.serve_loop import Request, make_serve_engine
    cfg = ARCHS["qwen3-8b"].reduced()
    engine = make_serve_engine(
        cfg, model.init_params(cfg, jax.random.PRNGKey(0)), slots=3,
        max_len=96, kv_layout="paged", block_size=8, prefill_chunk=16,
        decode_horizon=4)
    client = drive.Client(engine, Request, cfg.vocab_size, 7)
    name = "qwen3-8b.decode-backlog"
    tracer = run.Tracer(run.TRACE_DIR / name, 0.0, 1e9, 16)
    for rid, (p, o) in enumerate(((37, 9), (9, 6), (20, 11), (45, 7))):
        client.send(traffic.Spec(rid, p, o), client.clock())
    while client.inflight:
        tracer.tick(client)
        client.step(tracer.work if tracer.on else None, tracer.chunk)
    tracer.close(client)

    r = measured.Run({"name": name}, {}, {"kind": "backlog"}, {}, {}, 1,
                     list(client.records.values()), float("-inf"),
                     float("inf"), float("inf"), 0.0, client.decode_rows)
    eng = spans.of_run(r)
    assert len(eng.steps) == len(client.decode_rows)
    host = run.reader("host_ms_per_step.batch")(r)
    assert host == run.reader("host_ms_per_step.chat")(r)
    steps_ms = [1000.0 * st.span.duration_s for st in eng.steps]
    assert 0.0 < host < sum(steps_ms) / len(steps_ms)
    # the engine's own count agrees with the client's token stamps
    assert run.reader("decode_rows.batch")(r) == pytest.approx(
        r.decode_occupancy())
    chunks = eng.spans("serve.prefill_chunk")
    assert sum(s.stats["tokens"] for s in chunks) == 37 + 9 + 20 + 45
    assert sum(s.stats["tokens"] for s in eng.spans("serve.replay")) == (
        sum(o - 1 for o in (9, 6, 11, 7)))
