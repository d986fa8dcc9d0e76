"""Pallas TPU flash attention (causal / sliding-window / GQA).

TPU-native tiling: the grid is (batch*q_heads, Sq/block_q, Sk/block_k) with
the KV dimension innermost and sequential ("arbitrary"), so the running
online-softmax statistics (m, l) and the output accumulator live in VMEM
scratch across KV steps.  BlockSpec index maps stream one (block_q, hd)
Q-tile and one (block_k, hd) KV-tile into VMEM per step; GQA is expressed
in the K/V index maps (q head h reads kv head h // G) so grouped KV is
never materialized per-q-head in HBM.

Block shapes are the VMEM working set:  f32 scratch (block_q·hd + 2·block_q)
+ tiles (block_q + 2·block_k)·hd·2B.  The defaults (block_q=block_k=128,
MXU-aligned) use ~200 KB of ~16 MB VMEM, leaving room for double buffering.

Fully-masked KV tiles (causal: k-tile entirely after the q-tile; SWA:
k-tile entirely outside the window) are skipped with @pl.when — this is
what makes SWA attention O(S·w) instead of O(S²) at the kernel level.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # (block_q, hd), (block_k, hd), (block_k, hd)
    o_ref,  # (block_q, hd)
    m_scr, l_scr, acc_scr,  # VMEM scratch: (block_q, 1), (block_q, 1), (block_q, hd)
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    n_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # tile-level skip: entirely above the diagonal / outside the window
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window is not None:
        # newest key this tile could see: q_end; oldest: q_start - window + 1
        needed = jnp.logical_and(needed, k_start + block_k > q_start - window + 1)

    @pl.when(needed)
    def compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (block_q, hd)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (block_q, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def finish():
        # rows with no valid key (can't happen for causal self-attn) -> 0
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, KV, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """pl.pallas_call wrapper.  Sq/Sk are padded to block multiples; GQA via
    index maps.  interpret=True (default here) runs the kernel body in
    Python on CPU — the container has no TPU; on hardware pass False."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        # padded keys must never win the max: rely on causal mask (padded
        # q-rows are sliced off; padded k-cols are masked because kpos>qpos
        # for causal). For non-causal (encoder) we mask via window=None and
        # explicit validity below.
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k
    n_q, n_k = Sq_p // block_q, Sk_p // block_k

    if not causal and pad_k:
        raise ValueError("non-causal flash requires Sk % block_k == 0")

    # layout: fold head into leading grid dim; block over (S, hd)
    qg = q.transpose(0, 2, 1, 3).reshape(B * H, Sq_p, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk_p, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk_p, hd)

    grid = (B * H, n_q, n_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=hd ** -0.5,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (bh // G, ki, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, qi, ki: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qg, kg, vg)
    out = out.reshape(B, H, Sq_p, hd).transpose(0, 2, 1, 3)
    return out[:, :Sq]
