"""ISSUE 10 tentpole: the virtual-time flight recorder.

Three exact (``==``, no tolerances) acceptance properties, swept across
the condition x sync x granularity x engine matrix:

1. **Trace parity** — both projections of one ``DataPlaneSpec`` emit
   bit-identical canonical event streams (``repro.obs.parity``), and the
   scalar and vector engines synthesize the same streams from entirely
   different execution shapes.
2. **Ledger reconciliation** — summing the per-request cost ledger built
   from the trace reproduces ``StoreStats.class_a_requests`` /
   ``class_b_requests`` exactly (every charge has an emitting event).
3. **Observer purity** — ``trace=None`` and ``trace=TraceRecorder()``
   produce byte-identical stats, tiers and store counters (the recorder
   observes the schedule, never perturbs it).

Plus the exporters: Chrome trace-event JSON validates and round-trips
losslessly, and the wall-time decomposition sums spans back to
``EpochStats.wall_seconds``.
"""
import dataclasses
import json

import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # offline container
    from _hypothesis_fallback import given, settings
    from _hypothesis_fallback import strategies as st

from repro.core import MNIST, EpochStats, straggler_profiles
from repro.obs.events import TraceRecorder, canonical_stream
from repro.obs.export import (
    chrome_trace,
    decomposition,
    decomposition_table,
    events_from_chrome,
    text_timeline,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.obs.ledger import assert_reconciles, build_ledger, per_node_totals
from repro.obs.parity import assert_trace_parity, run_trace_parity
from repro.pipeline import condition

#: The tentpole matrix: demand-only, paper prefetch, single-node-horizon
#: oracle, cross-rank clairvoyant planner, and gradient-bucket overlap —
#: each exercising a different set of emitting components.
CONDITIONS = (
    ("cache", {"cache_items": 64}),
    ("fifty-fifty", {"cache_items": 64}),
    ("oracle", {"cache_items": 64}),
    ("cluster-oracle", {"cache_items": 64}),
    ("overlap", {"cache_items": 64}),
)
CONDITION_NAMES = tuple(name for name, _ in CONDITIONS)
_KW = dict(CONDITIONS)

_W = MNIST.scaled(0.01)  # 600 samples, 3 nodes, batch 64 — fast but real


def _spec(name, sync, granularity, engine, seed):
    spec = condition(name, _W, seed=seed, **_KW[name])
    if name == "overlap":
        sync = "batch"  # overlap="buckets" requires per-batch barriers
    return dataclasses.replace(
        spec, sync=sync, granularity=granularity, engine=engine
    )


def _traced_sim_run(spec, epochs=2):
    rec = TraceRecorder()
    stats, store = dataclasses.replace(spec, trace=rec).build_sim().run(
        epochs=epochs
    )
    return rec, stats, store


# ---------------------------------------------------------------------------
# 1. Event-level parity, sim vs runtime AND scalar vs vector.
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    name=st.sampled_from(CONDITION_NAMES),
    sync=st.sampled_from(["epoch", "batch"]),
    granularity=st.sampled_from(["step", "substep"]),
    engine=st.sampled_from(["scalar", "vector"]),
    seed=st.integers(min_value=0, max_value=4),
)
def test_trace_parity_matrix(name, sync, granularity, engine, seed):
    """The two projections emit identical canonical streams — compared
    with ``==`` on every event's (node, t, kind, dur, attrs)."""
    assert_trace_parity(_spec(name, sync, granularity, engine, seed), epochs=2)


@settings(max_examples=12, deadline=None)
@given(
    name=st.sampled_from(CONDITION_NAMES),
    sync=st.sampled_from(["epoch", "batch"]),
    seed=st.integers(min_value=0, max_value=3),
)
def test_trace_engine_equivalence(name, sync, seed):
    """Scalar stepping and vector segment-commit synthesis produce the
    same event multiset: the vector engine reconstructs per-sample demand
    spans, compute boundaries and cache inserts from its cumsum arrays."""
    scalar, _, _ = _traced_sim_run(_spec(name, sync, "step", "scalar", seed))
    vector, _, _ = _traced_sim_run(_spec(name, sync, "step", "vector", seed))
    assert canonical_stream(scalar.events) == canonical_stream(vector.events)


def test_trace_parity_under_stragglers():
    """Heterogeneous profiles skew every per-node float; the streams must
    still match event for event."""
    profs = straggler_profiles(_W.n_nodes, (0,), 2.0, 2.0)
    spec = dataclasses.replace(
        condition("fifty-fifty", _W, cache_items=64), nodes=profs, sync="batch"
    )
    assert_trace_parity(spec, epochs=2)


def test_trace_parity_report_diverged_renders():
    """A manufactured divergence is reported with the first differing
    event pair (not just a bare AssertionError)."""
    a, b = TraceRecorder(), TraceRecorder()
    a.emit("demand", 0, 1.0, 0.5, idx=3, tier="ram", class_b=0)
    b.emit("demand", 0, 1.0, 0.5, idx=4, tier="ram", class_b=0)
    from repro.obs.parity import TraceParityReport

    report = TraceParityReport(
        spec_label="manufactured",
        epochs=1,
        sim_stream=canonical_stream(a.events),
        runtime_stream=canonical_stream(b.events),
    )
    assert not report.exact
    pair = report.first_divergence()
    assert pair is not None and pair[0] != pair[1]
    assert "DIVERGED" in report.describe()


# ---------------------------------------------------------------------------
# 2. Ledger reconciliation: sum-of-ledger == counters, exactly.
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(
    name=st.sampled_from(CONDITION_NAMES),
    sync=st.sampled_from(["epoch", "batch"]),
    engine=st.sampled_from(["scalar", "vector"]),
    seed=st.integers(min_value=0, max_value=3),
)
def test_ledger_reconciles_counters(name, sync, engine, seed):
    spec = _spec(name, sync, "step", engine, seed)
    rec, stats, store = _traced_sim_run(spec)
    report = assert_reconciles(rec.events, store)
    assert report.n_lines > 0
    # The runtime projection's trace reconciles against ITS counters too.
    run_rec = TraceRecorder()
    with dataclasses.replace(spec, trace=run_rec).build_runtime() as cluster:
        _, run_store = cluster.run(epochs=2)
    assert_reconciles(run_rec.events, run_store)


def test_ledger_lines_attribute_every_charge():
    """Ledger lines split demand GETs from round issues and carry node +
    virtual-time provenance; per-node totals sum to the cluster total."""
    spec = condition("fifty-fifty", _W, cache_items=64)
    rec, _, store = _traced_sim_run(spec)
    lines = build_ledger(rec.events)
    assert {ln.kind for ln in lines} == {"issue", "demand"}
    assert all(ln.class_a >= 0 and ln.class_b >= 0 for ln in lines)
    per_node = per_node_totals(rec.events)
    assert sum(a for a, _ in per_node.values()) == store.class_a_requests
    assert sum(b for _, b in per_node.values()) == store.class_b_requests


# ---------------------------------------------------------------------------
# 3. Observer purity: tracing-off == tracing-on, byte for byte.
# ---------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(
    name=st.sampled_from(CONDITION_NAMES),
    engine=st.sampled_from(["scalar", "vector"]),
    seed=st.integers(min_value=0, max_value=3),
)
def test_tracing_off_equals_tracing_on(name, engine, seed):
    spec = _spec(name, "batch", "step", engine, seed)
    plain_stats, plain_store = spec.build_sim().run(epochs=2)
    _, traced_stats, traced_store = _traced_sim_run(spec)
    assert [s.asdict() for s in traced_stats] == [s.asdict() for s in plain_stats]
    assert (traced_store.class_a_requests, traced_store.class_b_requests,
            traced_store.bytes_read, traced_store.read_seconds) == (
        plain_store.class_a_requests, plain_store.class_b_requests,
        plain_store.bytes_read, plain_store.read_seconds)


def test_untraced_runtime_rejects_free_running_only():
    """trace= is a lock-step-only knob: the free-running threaded runtime
    has no virtual timeline to record and must refuse loudly."""
    from repro.core import RealClock

    spec = dataclasses.replace(
        condition("cache", _W, cache_items=64), trace=TraceRecorder()
    )
    with pytest.raises(ValueError, match="lock-step"):
        spec.build_runtime(clock=RealClock(scale=1e-4))


# ---------------------------------------------------------------------------
# EpochStats: wall_seconds + asdict round-trip (satellite 1).
# ---------------------------------------------------------------------------
def test_epoch_stats_wall_seconds_and_asdict_round_trip():
    s = EpochStats(
        epoch=1, node=2, samples=10,
        data_wait_seconds=0.5, compute_seconds=0.25,
        allreduce_wait_seconds=0.125, allreduce_comm_seconds=0.0625,
        evictions=3, tier_hits={"ram": 7, "bucket": 3},
    )
    assert s.wall_seconds == 0.5 + 0.25 + 0.125 + 0.0625
    assert s.wall_clock_seconds == s.wall_seconds  # legacy alias
    d = s.asdict()
    assert EpochStats(**d) == s
    d["tier_hits"]["ram"] = 0  # copied, never aliased
    assert s.tier_hits["ram"] == 7
    json.dumps(d)  # stable plain-dict form is JSON-serializable


def test_epoch_stats_asdict_round_trips_from_real_run():
    stats, _ = condition("cache", _W, cache_items=64).build_sim().run(epochs=2)
    for s in stats:
        assert EpochStats(**s.asdict()) == s


# ---------------------------------------------------------------------------
# Exporters: Chrome trace-event JSON + text views.
# ---------------------------------------------------------------------------
def test_chrome_export_validates_and_round_trips(tmp_path):
    spec = dataclasses.replace(
        condition("overlap", _W, cache_items=64), sync="batch"
    )
    rec, stats, _ = _traced_sim_run(spec)
    path = tmp_path / "trace.json"
    write_chrome_trace(str(path), rec.events)
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) == []
    assert canonical_stream(events_from_chrome(doc)) == canonical_stream(rec.events)
    # One track per rank with the fixed lanes, metadata included.
    pids = {r["pid"] for r in doc["traceEvents"]}
    assert pids >= {1, 2, 3}  # one process per rank (pid = node + 1)
    names = {r["args"]["name"] for r in doc["traceEvents"] if r["ph"] == "M"
             and r["name"] == "thread_name"}
    assert names == {"data-wait", "compute", "allreduce", "events"}


def test_chrome_validation_catches_breakage():
    assert validate_chrome_trace({"nope": 1})
    doc = {"traceEvents": [{"name": "demand", "ph": "X", "ts": 1.0,
                            "pid": 1, "tid": 1}]}
    assert any("dur" in p for p in validate_chrome_trace(doc))
    doc = {"traceEvents": [
        {"name": "a", "ph": "i", "ts": 2.0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "i", "ts": 1.0, "pid": 1, "tid": 1},
    ]}
    assert any("monotone" in p for p in validate_chrome_trace(doc))


def test_decomposition_sums_back_to_wall_seconds():
    """Each traced span's duration is the very float the schedule added to
    the matching EpochStats field, so for a one-epoch run the linear fold
    over emission-ordered events reproduces every stats field with ==
    (overlap-exposed tails count as comm, mirroring the accounting)."""
    for name in ("fifty-fifty", "overlap"):
        spec = _spec(name, "batch", "step", "scalar", seed=0)
        rec, stats, _ = _traced_sim_run(spec, epochs=1)
        dec = decomposition(rec.events)
        for s in stats:
            d = dec[s.node]
            assert d["data_wait"] == s.data_wait_seconds
            assert d["compute"] == s.compute_seconds
            assert d["allreduce_wait"] == s.allreduce_wait_seconds
            assert d["allreduce_comm"] == s.allreduce_comm_seconds
            assert (d["data_wait"] + d["compute"] + d["allreduce_wait"]
                    + d["allreduce_comm"]) == s.wall_seconds


def test_text_views_render(tmp_path, capsys):
    spec = condition("fifty-fifty", _W, cache_items=64)
    rec, _, _ = _traced_sim_run(spec, epochs=1)
    table = decomposition_table(rec.events)
    assert "data_wait" in table and "rank" in table
    timeline = text_timeline(rec.events, limit=5)
    assert len(timeline.splitlines()) == 5
    # CLI end-to-end: render + validate.
    path = tmp_path / "t.json"
    write_chrome_trace(str(path), rec.events)
    from repro.obs.__main__ import main

    assert main([str(path), "--limit", "3"]) == 0
    out = capsys.readouterr().out
    assert "wall-time decomposition" in out and "timeline" in out
    assert main([str(path), "--validate"]) == 0
    assert "valid Chrome trace" in capsys.readouterr().out


def test_run_trace_parity_report_describes_exact():
    report = run_trace_parity(condition("cache", _W, cache_items=64), epochs=1)
    assert report.exact
    assert "EXACT" in report.describe()
    assert report.first_divergence() is None
