"""The benchmark's Table-I bucket, and the data check that runs in the window."""
import math

import pytest

from bench import harness, payloads
from bench.bucket import TableIBucket
from bench.tests.smoke import smoke_cell

TRAFFIC = sorted({c["traffic"] for c in harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"]})


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_table_i_endpoints(traffic):
    """784 B objects read at 49.80 kB/s in sequence and 281.73 kB/s over 16
    connections, as Table I measures them; a 8196 B token object costs
    15.70 + 0.41 ms; a pool larger than 16 reads as 16."""
    b = TableIBucket(**harness.load_json(harness.BENCH / "traffic" / f"{traffic}.json")["bucket"])
    assert math.isclose(784 / b.get_seconds(784), 49.80e3, rel_tol=1e-12)
    assert math.isclose(784 / b.get_seconds(784) * b.parallel_efficiency(16), 281.73e3, rel_tol=1e-4)
    assert b.get_seconds(8196) == b.request_latency_s + 8196 / 20e6
    assert b.bulk_get_seconds([8196] * 512, 64) == pytest.approx(
        512 * b.get_seconds(8196) / 16 ** b.parallel_alpha, rel=1e-12
    )
    assert b.bulk_get_seconds([], 16) == 0.0
    assert b.list_seconds(8192) == 9 * 0.05


def test_the_data_plane_reads_through_the_cells_bucket():
    from repro.core import RealClock

    cell = smoke_cell("mamba2-130m.bucket-direct")
    tokens = payloads.make_tokens(64, 8, 100, 1)
    cluster = harness.build_spec(cell, payloads.as_objects(tokens), 1).build_runtime(RealClock())
    store = cluster.buckets[0]
    assert store.model == TableIBucket(**cell.traffic["bucket"])
    assert store.get(3) == tokens[3].tobytes()
    assert store.stats.class_b_requests == 1
    assert store.stats.read_seconds == store.model.get_seconds(tokens[3].nbytes)


def test_data_check_counts_order_tokens_and_rows():
    tokens = payloads.make_tokens(16, 8, 100, 2)
    order = payloads.epoch_order(16, 3, 0, 0, 1)
    sound = harness.DataCheck(tokens, order)
    sound.serve(list(order[:4]))
    for i in order[:4]:
        sound.decode(tokens[i])
    assert sound.mismatch == 0

    bad = harness.DataCheck(tokens, order)
    bad.serve([order[1], order[0], order[2]])  # two served out of order
    altered = tokens[order[0]].copy()
    altered[1] += 1
    bad.decode(altered)  # one token altered
    bad.decode(tokens[order[1]])  # and the third row served is never decoded
    assert bad.mismatch == 2 + 1 + 1
    assert isinstance(bad.mismatch, int)
