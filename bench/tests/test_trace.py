"""The trace reduction, on hand-made events and on a trace recorded on a TPU v5e.

``data/smoke_window.xplane.pb.gz`` is the profiler's trace of a harness
window at smoke size (mamba2 widths cut to 64, SSD kernel on) on one chip.
"""
import copy
import gzip
import pathlib
import types

import pytest

from bench import harness, trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "smoke_window.xplane.pb.gz"


def _event(name, a, b):
    return types.SimpleNamespace(name=name, start_ns=a, end_ns=b)


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=n, events=e) for n, e in lines]
    )


def test_hand_made_window():
    ops = [
        _event("%while.1 = (s32[]) while(...)", 100, 400),
        _event("%fusion.2 = f32[8] fusion(...)", 120, 200),
        _event("%ssd_scan.3 = bf16[8] custom-call(...)", 250, 300),
        _event("%copy.4 = f32[8] copy(...)", 600, 700),
        _event("%fusion.5 = f32[8] fusion(...)", 950, 1200),  # half outside the window
    ]
    host = [
        _event("bench.window", 0, 1000),
        _event("bench.next_batch", 400, 580),  # most of the gap 400..600
        _event("bench.decode", 700, 720),  # a small part of the gap 700..950
    ]
    data = types.SimpleNamespace(
        planes=[
            _plane("/device:TPU:0", [("XLA Ops", ops), ("Steps", [])]),
            _plane("/host:CPU", [("main", host)]),
        ]
    )
    s = trace.summarize(data)
    assert s.window_s == pytest.approx(1000e-9)
    # busy: 100..400, 600..700, 950..1000
    assert s.busy_s == pytest.approx(450e-9)
    # the while's own time excludes the fusion and the kernel inside it
    assert s.ops["while.1"] == (1, pytest.approx(170e-9))
    assert s.op_time(lambda n: n.startswith("ssd_scan")) == (1, pytest.approx(50e-9))
    assert s.gaps == [
        ("trainer", pytest.approx(250e-9)),
        ("bench.next_batch", pytest.approx(200e-9)),
        ("trainer", pytest.approx(100e-9)),
    ]


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return trace.summarize(ProfileData.from_serialized_xspace(gzip.decompress(DATA.read_bytes())))


def test_recorded_trace(recorded):
    assert 0 < recorded.busy_s < recorded.window_s
    assert sum(s for _, s in recorded.ops.values()) == pytest.approx(recorded.busy_s)
    calls, seconds = recorded.op_time(lambda n: n.startswith("ssd_scan"))
    assert calls > 0 and 0 < seconds < recorded.busy_s
    assert {g for g, _ in recorded.gaps} <= {"trainer", "bench.next_batch", "bench.decode"}
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0].startswith("ssd_scan")


def test_roofline_on_recorded_trace(recorded):
    from bench.tests.smoke import smoke_cell

    cell = smoke_cell("mamba2-130m.bucket-prefetch")
    run = types.SimpleNamespace(cell=cell, trace=recorded, device={"kind": "TPU v5 lite"})
    reader = harness.load_module(harness.BENCH / "metrics" / "ssd_fwd_roofline.py")
    share = reader.read(run)
    assert 0 < share <= 100
    idle = harness.load_module(harness.BENCH / "metrics" / "device_idle_share.py").read(run)
    assert idle == pytest.approx(100 * (1 - recorded.busy_s / recorded.window_s))
    no_kernel = copy.copy(recorded)
    no_kernel.ops = {n: v for n, v in recorded.ops.items() if not n.startswith("ssd_scan")}
    run.trace = no_kernel
    assert reader.read(run) is None
