"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to False when JAX's default backend is a TPU (the
kernels compile through Mosaic) and to True elsewhere, where the tests run
them in interpret mode — pass explicitly to override.  These are the
functions the models call when ``use_pallas`` is enabled (the SSD scan via
``models.ssm.ssd_pallas``, which adds its backward pass; the grouped
matmul of the dropless MoE layer); each has a
pure-jnp oracle in kernels/ref.py with identical signature/semantics.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gmm import grouped_matmul as _grouped_matmul
from repro.kernels.ssd import ssd_scan as _ssd


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q, k, v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """Blocked online-softmax attention. q (B,Sq,H,hd); k/v (B,Sk,KV,hd)."""
    if interpret is None:
        interpret = _default_interpret()
    return _flash(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk: int = 128, interpret: Optional[bool] = None):
    """Mamba-2 SSD chunked scan. Returns (y (B,S,H,P), state (B,H,P,N))."""
    if interpret is None:
        interpret = _default_interpret()
    return _ssd(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=interpret)


def grouped_matmul(lhs, rhs, group_sizes, *, tm: int = 128, interpret: Optional[bool] = None):
    """Rows by group times each group's weight, differentiable (kernels/gmm.py).
    lhs (M, K), rhs (G, K, N), group_sizes (G,) -> (M, N)."""
    if interpret is None:
        interpret = _default_interpret()
    return _grouped_matmul(lhs, rhs, group_sizes, tm, interpret)
