"""Faults planted under the timed path, to show that the check catches them.

Each is a context manager that breaks the program where the fault would
arise and restores it on exit.  ``bench/calibrate.py`` reads them on the
chip to set the limits; ``bench/tests/test_runs.py`` sees ``correct``
come out false with each.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(owner, name: str, value):
    old = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, old)


def _broken_step(wrap):
    """The trainer's step, built as it builds it, then wrapped by ``wrap``."""
    from repro.training import loop

    make = loop.make_train_step

    def make_broken(*args, **kwargs):
        return wrap(make(*args, **kwargs))

    return _patched(loop, "make_train_step", make_broken)


def unchanged_state():
    """A step that computes its loss and returns its state unchanged."""

    def wrap(step):
        def broken(params, opt_state, batch):
            loss, _, _ = step(params, opt_state, batch)
            return loss, params, opt_state

        return broken

    return _broken_step(wrap)


def half_batch():
    """A step that leaves out half of the batch and averages over the rest."""

    def wrap(step):
        def broken(params, opt_state, batch):
            return step(params, opt_state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

        return broken

    return _broken_step(wrap)


@contextlib.contextmanager
def altered_token():
    """The bucket serves the first object it is asked for with one token
    changed, every time it is asked for it."""
    import numpy as np

    from repro.core.store import SimulatedBucketStore

    first = []
    get, bulk_get = SimulatedBucketStore.get, SimulatedBucketStore.bulk_get

    def alter(index, payload):
        if not first:
            first.append(index)
        if index != first[0]:
            return payload
        row = np.frombuffer(payload, np.int32).copy()
        row[1] += 1
        return row.tobytes()

    def patched_get(self, index, *args, **kwargs):
        return alter(index, get(self, index, *args, **kwargs))

    def patched_bulk_get(self, indices, *args, **kwargs):
        return [alter(i, p) for i, p in zip(indices, bulk_get(self, indices, *args, **kwargs))]

    with _patched(SimulatedBucketStore, "get", patched_get):
        with _patched(SimulatedBucketStore, "bulk_get", patched_bulk_get):
            yield


FAULTS = {
    "unchanged_state": unchanged_state,
    "half_batch": half_batch,
    "altered_token": altered_token,
}
