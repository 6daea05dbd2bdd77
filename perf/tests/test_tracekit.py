"""The reduction from a trace to the per-layer metrics, on synthetic
intervals and on a trace recorded on the chip (``data/``)."""

from pathlib import Path

import pytest

from perf import run as harness
from perf import tracekit

DATA = Path(__file__).parent / "data"


def _reader(name):
    return harness.load_module(harness.ROOT / "perf" / "metrics" / f"{name}.py")


def test_union_overlap_gaps():
    m = tracekit.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m == [(0, 3), (5, 8)]
    assert tracekit.total(m) == 6
    assert tracekit.overlap(m, [(2, 6)]) == 2
    assert tracekit.gaps(m, 0, 10) == [(3, 5), (8, 10)]


def _synthetic():
    # Two chips, a 100 ns window, two steps. Chip 0: fusion 0-40, a2a
    # 30-60 (10 ns hidden under the fusion), fusion 70-90. Chip 1: fusion
    # 0-50, a2a 50-55.
    ops = {"/device:TPU:0": [["fusion.1", "fusion", 0, 40],
                             ["all_to_all.2", "all-to-all", 30, 60],
                             ["fusion.1", "fusion", 70, 90]],
           "/device:TPU:1": [["fusion.1", "fusion", 0, 50],
                             ["all_to_all.2", "all-to-all", 50, 55]]}
    host = [["window", 0, 100], ["sync", 60, 70]]
    return tracekit.Trace((0, 100), ops, host)


def _run(trace, steps=2, min_step_s=5e-9):
    return harness.Run(steps=steps, window_s=1e-7, setup_s=1.0,
                       plan_s=0.5, flops_per_step=1.0,
                       min_step_s=min_step_s, memory_peak_bytes=None,
                       trace=trace)


def test_readers_on_synthetic_trace():
    run = _run(_synthetic())
    # chip 0 is busy 0-60 and 70-90: 80 of 100 ns
    assert _reader("device_idle_share").read(run) == pytest.approx(20.0)
    # 5 ns of roofline per step over 40 ns of busy time per step
    assert _reader("hbm_roofline_share").read(run) == pytest.approx(12.5)
    # chip 0 spends 30 ns in the all-to-all, 20 of them alone
    assert _reader("exchange_ms").read(run) == pytest.approx(15e-6)
    assert _reader("exchange_exposed_ms").read(run) == pytest.approx(10e-6)
    b = tracekit.breakdown(run.trace, chips=2)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(55e-9)]
    assert b["idle_gaps"] == [["sync", pytest.approx(10e-9)],
                              ["window", pytest.approx(10e-9)]]


def test_readers_find_nothing_without_trace_or_collectives():
    run = _run(None)
    for name in ("device_idle_share", "hbm_roofline_share", "exchange_ms",
                 "exchange_exposed_ms"):
        assert _reader(name).read(run) is None
    t = _synthetic()
    t.ops = {d: [e for e in evs if e[1] == "fusion"] for d, evs in t.ops.items()}
    assert _reader("exchange_ms").read(_run(t)) is None
    assert _reader("exchange_exposed_ms").read(_run(t)) is None


def test_readers_on_recorded_chip_trace():
    """``perf/run.py --workload c2c512-fwd --seconds 1 --trace 1`` on the
    v5e (my chip run, PR 22): 15 transforms in the window."""
    trace = tracekit.load_xplane(DATA / "c2c512-fwd.xplane.pb")
    assert list(trace.ops) == ["/device:TPU:0"]
    assert trace.window_s == pytest.approx(1.219235767)
    assert sum(1 for n, *_ in trace.ops["/device:TPU:0"]
               if n == "custom-call.2") == 15
    run = _run(trace, steps=15, min_step_s=2 * 2**30 / 819e9)
    assert _reader("device_idle_share").read(run) == pytest.approx(
        0.1786696272338073, rel=1e-9)
    assert _reader("hbm_roofline_share").read(run) == pytest.approx(
        3.2316638097790586, rel=1e-9)
    assert _reader("exchange_ms").read(run) is None  # one chip, no exchange
    b = tracekit.breakdown(trace, chips=1)
    assert b["device_ops"][0] == ["custom-call.2",
                                  pytest.approx(0.092028851, rel=1e-6)]
    assert b["idle_gaps"][0] == ["sync", pytest.approx(0.002062096, rel=1e-6)]


def test_exchange_split_on_recorded_four_chip_trace():
    """The first four transforms of a ``c2c1024-slab4 --trace 1`` run on
    four v5e chips (my chip run, PR 22), kept as a ``Trace``: the t2
    all-to-all runs as ``all_to_all.24``/``.28``, alone on each chip."""
    trace = tracekit.Trace.from_json(
        (DATA / "c2c1024-slab4.trace.json").read_text())
    assert len(trace.ops) == 4
    run = _run(trace, steps=4, min_step_s=4 * 2**30 / 819e9)
    assert _reader("exchange_ms").read(run) == pytest.approx(
        24.671447, rel=1e-6)
    assert _reader("exchange_exposed_ms").read(run) == pytest.approx(
        24.671447, rel=1e-6)
    assert _reader("device_idle_share").read(run) == pytest.approx(
        0.0059, abs=1e-3)
    assert _reader("hbm_roofline_share").read(run) == pytest.approx(
        100 * (4 * 2**30 / 819e9) / (0.779200575 / 4), rel=1e-6)
    ops = tracekit.breakdown(trace, chips=4)["device_ops"]
    assert [n for n, _ in ops[:3]] == ["custom-call.4", "all_to_all.24",
                                       "all_to_all.28"]
