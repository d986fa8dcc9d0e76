"""Cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds.

Only the tests use these: widths, depth and vocabulary are cut by the
model file's ``SMOKE``, sequence and dataset here, and the bucket answers in
a tenth of a millisecond.  The harness, the program's path and the check are
the cell's own.
"""
import copy
import time

import jax

from bench import harness

_load_cell = harness.load_cell  # tests may patch harness.load_cell with smoke_cell


def smoke_cell(name: str) -> harness.Cell:
    cell = _load_cell(name)
    conf, traffic = copy.deepcopy(cell.conf), copy.deepcopy(cell.traffic)
    conf.update(cell.model.SMOKE)
    conf["train"].update(seq_len=64, batch=4)
    traffic.update(n_objects=2048)
    traffic["bucket"].update(request_latency_s=1e-4, listing_latency_s=1e-4)
    if traffic["cache_items"] is not None:
        traffic.update(cache_items=64, fetch_size=32, prefetch_threshold=32)
    cell.conf, cell.traffic = conf, traffic
    return cell


def smoke_run(name: str, seed: int = 2 ** 31 + 5, seconds: float = 0.3):
    """One run of the cell on whatever devices JAX has (the CPU here)."""
    return harness.run_cell(
        smoke_cell(name), seed, seconds, False, time.perf_counter(), jax.devices()
    )
