"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid: (batch, heads, S/chunk) with the chunk dimension innermost and
sequential ("arbitrary") — the (P, N) SSD state lives in VMEM scratch and
is carried across chunk steps, exactly the inter-chunk recurrence of the
SSD algorithm.  Per step the kernel does four MXU matmuls per head:

    cb   = C  B^T                (Q,N)x(N,Q)   intra-chunk scores
    y    = (cb * L * dt) x       (Q,Q)x(Q,P)   intra-chunk output
    y   += (C S^T) * exp(a_cum)  (Q,N)x(N,P)   inter-chunk output
    S'   = exp(a_tot) S + x^T(w*B)  (P,Q)x(Q,N) state update

VMEM working set per step: x (Q,P) + B,C (Q,N) + state (P,N) f32 + the
(Q,Q) decay matrix — with Q=128, P=64, N=128 that is ~260 KB, comfortably
inside the ~16 MB VMEM budget with double buffering.

Heads are gridded individually (block_h == 1): every matmul above is then a
clean 2-D MXU op; B/C index maps select the head's group (G | H), so grouped
B/C are never materialized per head in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,  # (Q, P)   this (b, h, chunk)'s inputs
    dt_ref,  # (Q, 1)
    A_ref,  # (1, H)   SMEM: per-head decay scalars, whole array
    B_ref,  # (Q, N)
    C_ref,  # (Q, N)
    D_ref,  # (1, H)   SMEM: per-head skip scalars, whole array
    y_ref,  # (Q, P)   output
    st_ref,  # (P, N)  final-state output (written on last chunk)
    state,  # VMEM scratch (P, N) f32: the carried SSD state
    *,
    chunk: int,
    n_chunks: int,
):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)  # (Q, 1)
    A = A_ref[0, h]
    Bm = B_ref[0, 0].astype(jnp.float32)  # (Q, N)
    Cm = C_ref[0, 0].astype(jnp.float32)

    # Mosaic lowers neither cumsum nor a (Q, 1) -> (1, Q) reshape, so the
    # prefix sum is a masked lane reduction and row forms come from
    # transposing a (Q, Q) broadcast.
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = ii >= jj

    def rows(col):  # (Q, 1) -> (Q, Q) with [i, j] = col[j]
        return jnp.broadcast_to(col, (chunk, chunk)).T

    a = dt * A  # (Q, 1) log-decay per step
    a_cum = jnp.sum(jnp.where(causal, rows(a), 0.0), axis=1, keepdims=True)  # (Q, 1)
    a_tot = jnp.sum(a, axis=0, keepdims=True)  # (1, 1)

    # intra-chunk: L[i,j] = exp(a_i - a_j) for i >= j
    seg = a_cum - rows(a_cum)  # (Qi, Qj)
    L = jnp.exp(jnp.where(causal, seg, -jnp.inf))  # no inf above the diagonal
    cb = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Qi, Qj)
    M = cb * L * rows(dt)
    y = jax.lax.dot_general(
        M, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    # inter-chunk: y_i += exp(a_cum_i) * C_i . S^T
    cs = jax.lax.dot_general(
        Cm, state[...], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)
    y = y + cs * jnp.exp(a_cum)

    # state update: S' = exp(a_tot) S + x^T (w * B), w = exp(a_tot - a_cum) dt
    w = jnp.exp(a_tot - a_cum) * dt  # (Q, 1)
    su = jax.lax.dot_general(
        x, Bm * w, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    state[...] = state[...] * jnp.exp(a_tot) + su

    y_ref[0, 0] = (y + x * D_ref[0, h]).astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def emit_state():
        st_ref[0, 0] = state[...].astype(st_ref.dtype)


def ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, S, G, N)
    Cm: jax.Array,  # (B, S, G, N)
    D: jax.Array,  # (H,)
    *,
    chunk: int = 128,
    interpret: bool = True,
):
    """pl.pallas_call wrapper. Returns (y (B,S,H,P), final_state (B,H,P,N)).

    S must be a chunk multiple (callers pad, as models/ssm.py does).
    """
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    xg = x.transpose(0, 2, 1, 3)  # (B, H, S, P)
    dtg = dt.transpose(0, 2, 1)[..., None]  # (B, H, S, 1)
    Bg = Bm.transpose(0, 2, 1, 3)  # (B, G, S, N)
    Cg = Cm.transpose(0, 2, 1, 3)
    # Per-head scalars go to SMEM whole: a (1, 1) VMEM block of an (H, 1)
    # array breaks the TPU's (8, 128) tiling rule, and SMEM is where scalars
    # that steer vector math belong.
    A2 = A.astype(jnp.float32).reshape(1, H)
    D2 = D.astype(jnp.float32).reshape(1, H)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    grid = (B, H, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, st = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            smem,
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xg, dtg, A2, Bg, Cg, D2)
    return y.transpose(0, 2, 1, 3), st
