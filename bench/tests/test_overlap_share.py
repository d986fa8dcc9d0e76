"""The reader of the trainer's overlap counter."""
import types

import pytest

from bench import harness


def _run(steps):
    return types.SimpleNamespace(steps=steps)


def test_overlap_share_reads_the_counter():
    reader = harness.load_module(harness.BENCH / "metrics" / "overlap_share.py")
    steps = [types.SimpleNamespace(overlapped=o) for o in (True, True, False, True)]
    assert reader.read(_run(steps)) == pytest.approx(75.0)
    assert reader.read(_run(steps[:2])) == pytest.approx(100.0)
    old = [types.SimpleNamespace(data_wait_s=0.1, compute_s=0.4)]  # a program without the counter
    assert reader.read(_run(old)) is None
    assert reader.read(_run([])) is None
