"""The benchmark's own data: token objects, seeds and the sampler's order.

The bucket objects are packed ``seq_len + 1``-token int32 sequences in which
every odd position repeats its predecessor, so that the loss can fall.  The
data plane is handed them as ``payload_factory``; the check reads them back
from here, and from nowhere in the program.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass(frozen=True)
class Seeds:
    """Independent 32-bit streams drawn from one ``--seed`` of any size."""

    data: int
    sampler: int
    params: int

    @classmethod
    def from_seed(cls, seed: int) -> "Seeds":
        data, sampler, params = np.random.SeedSequence(seed).generate_state(3)
        return cls(int(data), int(sampler), int(params))


def make_tokens(n_objects: int, seq_len: int, vocab: int, seed: int) -> np.ndarray:
    """``(n_objects, seq_len + 1)`` int32 token rows with ids below ``vocab``."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, vocab, size=(n_objects, seq_len + 1), dtype=np.int32)
    base[:, 1::2] = base[:, 0:-1:2]
    return base


def as_objects(tokens: np.ndarray) -> Dict[int, bytes]:
    """One bucket object per row, keyed by its dataset index."""
    return {i: row.tobytes() for i, row in enumerate(tokens)}


def epoch_order(n_objects: int, seed: int, epoch: int, rank: int, world: int) -> np.ndarray:
    """The indices rank ``rank`` reads in ``epoch``: one seeded permutation of
    the dataset per epoch, cut to a multiple of ``world`` and dealt out in
    strides (PyTorch's ``DistributedSampler``, as arXiv:2108.06322 uses it)."""
    perm = np.random.default_rng((seed, epoch)).permutation(n_objects)
    usable = (n_objects // world) * world
    return perm[:usable][rank::world]
