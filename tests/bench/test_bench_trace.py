"""The trace reduction, on a small trace recorded on the chip and on
hand-made events.

``data/decode_backlog_v5e.xplane.pb`` is a 0.23 s sub-window of a traced
run on one TPU v5e: qwen3-8b's widths cut to 2 layers, 8 slots, the
decode-backlog traffic, five fused decode calls (bench/run.py with the
traced sub-window shortened)."""

import pathlib

import pytest

from bench import measured, run, trace_reduce as tr

FIXTURE = (pathlib.Path(__file__).parent / "data"
           / "decode_backlog_v5e.xplane.pb")


@pytest.fixture(scope="module")
def chip():
    trace = tr.load(str(FIXTURE))
    lo, hi = trace.window()
    return trace, tr.summarize(trace, lo, hi)


def test_recorded_trace_has_one_chip_and_the_harness_span(chip):
    trace, s = chip
    assert sorted(trace.ops) == [0]
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.2294, abs=1e-3)


def test_busy_union_and_idle_share(chip):
    trace, s = chip
    lo, hi = trace.window()
    # the union never exceeds the window, nor the sum of the leaf ops
    assert 0.0 < s.busy_s <= s.window_s
    assert s.busy_s <= sum(s.op_s.values()) + 1e-9
    assert s.busy_s == pytest.approx(0.2068, abs=1e-3)
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    assert 0.05 < s.idle_share < 0.15


def test_per_kernel_and_per_program_time(chip):
    _, s = chip
    assert s.count(measured.KERNELS["decode"]) == 40     # 5 calls x 4 x 2
    assert s.time(measured.KERNELS["decode"]) == pytest.approx(0.1147,
                                                               abs=1e-3)
    assert s.count(measured.KERNELS["prefill"]) == 0
    assert s.count(measured.PROGRAMS["decode"], of="module") == 5
    assert s.time(measured.KERNELS["decode"]) < s.time(
        measured.PROGRAMS["decode"], of="module")
    assert s.device_ops[0][0] == "paged_attention_pallas"
    # loops that contain other operations are not counted as operations
    assert not any(name.startswith("while") for name in s.op_s)


def test_one_chip_has_no_collective_time(chip):
    _, s = chip
    assert s.collective_exposed_s == 0.0


def test_idle_gaps_are_attributed_to_host_spans(chip):
    _, s = chip
    names = dict(s.idle_gaps)
    assert sum(names.values()) == pytest.approx(s.window_s - s.busy_s,
                                                abs=1e-6)
    assert "np.asarray(jax.Array)" in names       # the engine's fence


def _ev(name, a, b):
    return tr.Event(name, a, b)


def test_union_and_subtract():
    u = tr.union([(0, 2), (1, 3), (5, 6), (9, 12)], 0.5, 10)
    assert u == [(0.5, 3), (5, 6), (9, 10)]
    assert tr.length(u) == pytest.approx(4.5)
    assert tr.subtract([(0, 10)], [(1, 2), (3, 4)]) == [(0, 1), (2, 3),
                                                        (4, 10)]
    assert tr.subtract([(0, 1)], [(0, 1)]) == []


def test_collective_share_counts_only_the_exposed_part():
    ops = {0: [_ev("fusion.1", 0.0, 4.0), _ev("all-reduce.2", 3.0, 6.0),
               _ev("fusion.3", 8.0, 9.0)],
           1: [_ev("all-gather.1", 0.0, 2.0)]}
    host = {"main": [_ev(tr.TRACED_SPAN, 0.0, 10.0),
                     _ev("bench.step", 0.0, 9.5)]}
    s = tr.summarize(tr.Trace(ops, {}, host), 0.0, 10.0)
    # chip 0: 2 s of all-reduce alone (4-6); chip 1: 2 s; mean 2 s
    assert s.collective_exposed_s == pytest.approx(2.0)
    assert s.busy_s == pytest.approx((7.0 + 2.0) / 2)
    # chip 0's idle gaps (6-8, 9-10) fall inside the step span
    assert dict(s.idle_gaps) == {"bench.step": pytest.approx(3.0)}


def test_collective_share_reads_the_exposed_part_of_the_window():
    ops = {0: [_ev("fusion.1", 0.0, 4.0), _ev("all-reduce.2", 3.0, 6.0)],
           1: [_ev("all-reduce.1", 0.0, 1.0), _ev("fusion.2", 1.0, 9.0)]}
    host = {"main": [_ev(tr.TRACED_SPAN, 0.0, 10.0)]}
    s = tr.summarize(tr.Trace(ops, {}, host), 0.0, 10.0)
    read = run.reader("collective_share.tp4")
    run_ = measured.Run({}, {}, {}, {}, {}, 2, [], 0.0, 10.0, 10.0, 1.0)
    assert read(run_) is None                      # no trace
    run_.trace = s
    # chip 0: 2 s exposed (4-6), chip 1: 1 s; mean 1.5 s of 10
    assert read(run_) == pytest.approx(15.0)


def test_collective_share_is_none_on_one_chip(chip):
    _, s = chip
    run_ = measured.Run({}, {}, {}, {}, {}, 1, [], 0.0, 1.0, 1.0, 1.0)
    run_.trace = s
    assert run.reader("collective_share.tp4")(run_) is None


def test_nested_operations_count_once():
    evs = [_ev("while.1", 0.0, 10.0), _ev("fusion.2", 1.0, 2.0),
           _ev("custom.3", 3.0, 7.0), _ev("fusion.4", 10.0, 11.0)]
    assert [e.name for e in tr.leaves(evs)] == ["fusion.2", "custom.3",
                                                 "fusion.4"]
    assert tr.short_name("%fusion.12 = bf16[2] fusion(%x)") == "fusion.12"
    assert tr.op_family("fusion.12") == "fusion"
