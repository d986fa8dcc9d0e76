"""The device's idle time split by the trainer's phase spans, and the
readers of the trainer's phase counters."""
import gzip
import types

import pytest

from bench import harness, phases, trace
from bench.tests.test_trace import DATA, _event, _plane

OPS = [
    _event("%while.1 = (s32[]) while(...)", 100, 400),
    _event("%fusion.2 = f32[8] fusion(...)", 120, 200),
    _event("%copy.4 = f32[8] copy(...)", 600, 700),
    _event("%fusion.5 = f32[8] fusion(...)", 950, 1200),  # half outside the window
]
HOST = [
    _event("bench.window", 0, 1000),
    # gap 0..100: no span open
    _event("train.next_batch", 390, 590),  # gap 400..600: 190 of it ...
    _event("bench.next_batch", 400, 580),  # ... but the harness's span names it
    _event("train.prepare", 690, 760),  # gap 700..950: 60 of it
    _event("bench.decode", 700, 720),
    _event("train.dispatch", 760, 800),  # 40
    _event("train.block", 800, 1010),  # 150: the most, so it names the gap
]


def _data(*device_planes):
    return types.SimpleNamespace(planes=[*device_planes, _plane("/host:CPU", [("main", HOST)])])


def test_hand_made_window_with_program_spans():
    data = _data(_plane("/device:TPU:0", [("XLA Ops", OPS)]))
    s = phases.split(data)
    assert s.idle_by_span == {
        "": pytest.approx(110e-9),  # 0..100 and 590..600
        "train.next_batch": pytest.approx(190e-9),
        "train.prepare": pytest.approx(60e-9),
        "train.dispatch": pytest.approx(40e-9),
        "train.block": pytest.approx(150e-9),
    }
    summary = trace.summarize(data)
    assert sum(s.idle_by_span.values()) == pytest.approx(summary.window_s - summary.busy_s)
    assert s.idle_outside("train.next_batch") == pytest.approx(360e-9)
    assert s.gaps == [
        ("train.block", pytest.approx(250e-9)),
        ("bench.next_batch", pytest.approx(200e-9)),
        ("trainer", pytest.approx(100e-9)),
    ]
    # The same gaps by the harness's spans alone: only "trainer" gaps change.
    assert [g for g, _ in summary.gaps] == ["trainer", "bench.next_batch", "trainer"]


def test_idle_is_a_mean_over_the_chips():
    busy = [_event("%fusion.9 = f32[8] fusion(...)", 0, 1000)]
    data = _data(
        _plane("/device:TPU:0", [("XLA Ops", OPS)]),
        _plane("/device:TPU:1", [("XLA Ops", busy)]),
        _plane("/device:TPU:2", [("XLA Ops", [])]),  # ran nothing: not counted
    )
    s = phases.split(data)
    summary = trace.summarize(data)
    assert s.idle_by_span["train.block"] == pytest.approx(75e-9)
    assert sum(s.idle_by_span.values()) == pytest.approx(summary.window_s - summary.busy_s)


def test_recorded_trace_without_program_spans():
    """A trace of a program without the phase spans puts all its idle time
    under no span, and names its gaps exactly as the summary does."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(gzip.decompress(DATA.read_bytes()))
    summary = trace.summarize(data)
    s = phases.split(data)
    assert list(s.idle_by_span) == [""]
    assert s.idle_by_span[""] == pytest.approx(summary.window_s - summary.busy_s, abs=1e-9)
    assert s.gaps == summary.gaps


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("metric,field", [("prepare_ms", "prepare_s"), ("dispatch_ms", "dispatch_s")])
def test_phase_counter_readers(metric, field):
    reader = _reader(metric)
    steps = [types.SimpleNamespace(**{field: s}) for s in (0.001, 0.002, 0.006)]
    assert reader.read(types.SimpleNamespace(steps=steps)) == pytest.approx(3.0)
    old = [types.SimpleNamespace(data_wait_s=0.1, compute_s=0.4)]  # a program without the counter
    assert reader.read(types.SimpleNamespace(steps=old)) is None
    assert reader.read(types.SimpleNamespace(steps=[])) is None
