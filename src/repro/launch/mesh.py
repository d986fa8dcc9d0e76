"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
before first jax init; tests and benches see the single real CPU device.

Production target: TPU v5e pods, 256 chips each.
  single pod:  (data=16, model=16)
  multi-pod:   (pod=2, data=16, model=16)

Hardware constants used by the roofline analysis live here too.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes GSPMD partitions (``AxisType.Auto``).

    The model code is written for the compiler to propagate shardings from
    ``with_sharding_constraint`` pins and to insert FSDP gathers itself.
    ``jax.make_mesh`` defaults to ``Explicit`` axes since JAX 0.7, under
    which every op must type-check its output sharding: the embedding
    gather of a ``P(tp, fsdp)`` table by data-sharded tokens then asks for
    ``P('data', None, 'data')`` and is refused.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    """The mesh axes the global batch is sharded over (FSDP axes)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def make_smoke_mesh(n_data: int = 1, n_model: int = 1):
    """Mesh over the local devices — integration tests and the four-chip
    data-parallel smoke."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_custom_mesh(n_data: int, n_model: int):
    """Arbitrary single-pod (data, model) split over 256 chips — the §Perf
    hillclimbing explores per-architecture mesh shapes (e.g. 32x8 when the
    head count doesn't divide 16, 256x1 pure-DP for sub-1B models)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """TPU v5e roofline constants (per chip)."""

    name: str = "tpu-v5e"
    peak_flops: float = 197e12  # bf16 FLOP/s
    hbm_bw: float = 819e9  # bytes/s
    ici_bw: float = 50e9  # bytes/s per link
    hbm_bytes: float = 16e9  # capacity


TPU_V5E = HardwareSpec()
