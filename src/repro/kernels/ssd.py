"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (forward).

Grid: (batch, head blocks, S/chunk), chunk innermost and sequential
("arbitrary"): the SSD state of the block's heads lives in VMEM scratch and
is carried across chunk steps, the inter-chunk recurrence of the SSD
algorithm.  A head block is ``hb`` consecutive heads of one B/C group
(``head_block`` picks ``hb``), so one grid step covers every head that
shares the step's B and C.

The kernel reads the model's own layout: x and y as (B, S, H*P) lane
blocks of hb*P, B and C as (B, S, G*N) lane blocks of N, the final state as
(B, H*P, N).  Only dt is transposed by the wrapper, to (B, H, S), a (hb, Q)
block whose rows are the heads (4 bytes a token a head).

Once per step, for the whole block:

    cb    = C B^T                        (Q,N)x(N,Q)  intra-chunk scores
    a_cum = prefix sum of dt*A over Q    (hb,Q)x(Q,Q) f32, precision HIGHEST
    and the column forms of a_cum and dt, one (hb,Q) transpose each.

Per head (Q = chunk, P = head dim, N = state):

    L     = exp(a_cum_i - a_cum_j), i >= j
    y     = (cb * L * dt) x              (Q,Q)x(Q,P)
    y    += (C S^T) * exp(a_cum)         (Q,N)x(N,P)
    S'    = exp(a_tot) S + x^T (w*B)     (P,Q)x(Q,N), w = exp(a_tot - a_cum) dt

Heads are handled in windows of 128 lanes (two heads at P=64): each
window's matmuls take the whole aligned window and each head keeps its own
lanes by a select, so no slice cuts a lane tile.  The state is kept
transposed, (N, hb*P), so that C S^T is a plain matmul over a window's
lanes; it is transposed back once, when the last chunk writes it out.
Everything but the bf16 inputs is f32.  The f32 matmuls keep Mosaic's
default precision; only the prefix sum, which the decays of a whole chunk
ride on, asks for HIGHEST.

VMEM working set per step: x and y blocks (Q, hb*P), B and C (Q, N), dt
(hb, Q), D (1, hb*P) and the final-state block (hb*P, N) f32, all double
buffered, plus the (N, hb*P) f32 state scratch.  ``head_block`` takes the
largest divisor of H/G whose working set fits ``VMEM_BUDGET`` and whose
blocks tile the TPU's (8, 128) layout.  At mamba2-130m widths (H 24, P 64,
N 128, chunk 128) the whole group fits: hb = 24, 4.1 MB, 128 grid steps at
batch 8 and sequence 2048.  At Jamba-1.5 widths (H 256, chunk 256) hb = 32.
Widths whose head blocks cannot tile (only the tests' tiny ones) take the
largest block that fits; interpret mode runs them, Mosaic would refuse them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Bytes of VMEM a grid step's blocks (double buffered) and state scratch
#: may take: half the 16 MB scoped default, the rest left to the kernel's
#: (Q, Q) and (Q, 128) temporaries.
VMEM_BUDGET = 8 * 2**20

_LANES = 128


def block_bytes(hb: int, P: int, N: int, chunk: int, itemsize: int) -> int:
    """VMEM one grid step holds for a block of ``hb`` heads."""
    lanes = hb * P
    blocks = (
        2 * chunk * lanes * itemsize  # x, y
        + 2 * chunk * N * itemsize  # B, C
        + hb * chunk * 4  # dt
        + lanes * 4  # D
        + lanes * N * 4  # final state
    )
    return 2 * blocks + lanes * N * 4


def head_block(H: int, G: int, P: int, N: int, chunk: int, itemsize: int) -> int:
    """Heads per grid step: a divisor of H/G, the largest that fits
    ``VMEM_BUDGET`` and tiles (rows of dt a multiple of 8, lanes of x a
    multiple of 128, or the whole head axis)."""
    rep = H // G
    fit = [d for d in range(1, rep + 1)
           if rep % d == 0 and block_bytes(d, P, N, chunk, itemsize) <= VMEM_BUDGET]
    tiled = [d for d in fit if d == H or (d % 8 == 0 and d * P % _LANES == 0)]
    return max(tiled or fit or [1])


def _dot(a, b, contract=((1,), (0,)), **kw):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32, **kw
    )


def _ssd_kernel(
    x_ref,  # (1, Q, hb*P) this block's inputs
    dt_ref,  # (1, hb, Q)
    A_ref,  # (hb, 1)
    B_ref,  # (1, Q, N)   the block's group
    C_ref,  # (1, Q, N)
    D_ref,  # (1, hb*P)   per-head skip, repeated over each head's lanes
    y_ref,  # (1, Q, hb*P) output
    st_ref,  # (1, hb*P, N) final-state output (written on the last chunk)
    state,  # VMEM scratch (N, hb*P) f32: the carried state, transposed
    *,
    chunk: int,
    heads: int,
    head_dim: int,
    n_chunks: int,
):
    Q, P = chunk, head_dim
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def init():
        state[...] = jnp.zeros_like(state)

    f32 = jnp.float32
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = ii >= jj

    Bm, Cm = B_ref[0], C_ref[0]  # (Q, N)
    # bf16 products are exact in f32, so C.B^T from the inputs as they come
    # is the f32 product.
    cb = _dot(Cm, Bm, ((1,), (1,)))  # (Qi, Qj)
    Cm, Bm = Cm.astype(f32), Bm.astype(f32)

    # The decay chain of every head at once, heads on rows.  Mosaic lowers
    # no cumsum: the prefix sum is a matmul with the causal mask, in f32.
    dt_r = dt_ref[0].astype(f32)  # (hb, Q)
    a_cum_r = _dot(
        dt_r * A_ref[...], causal.astype(f32), ((1,), (1,)),
        precision=jax.lax.Precision.HIGHEST,
    )  # (hb, Qi): sum over j <= i of dt_j A
    a_cum_c = a_cum_r.T  # (Q, hb)
    dt_c = dt_r.T
    a_tot = a_cum_c[Q - 1:, :]  # (1, hb)
    decay_c = jnp.exp(a_cum_c)  # inter-chunk output scale
    w_c = jnp.exp(a_tot - a_cum_c) * dt_c  # state-update weights
    decay_tot = jnp.exp(a_tot)

    # Windows of whole heads, 128 lanes where P divides 128.
    per_win = math.gcd(heads, max(1, _LANES // P))
    W = per_win * P
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    for w0 in range(0, heads, per_win):
        lanes = pl.ds(w0 * P, W)
        x = x_ref[0, :, lanes].astype(f32)  # (Q, W)
        s = state[:, lanes]  # (N, W)
        cs = _dot(Cm, s)  # (Q, W): C S^T of each head in its lanes
        for h in range(w0, w0 + per_win):
            col = a_cum_c[:, h:h + 1]
            L = jnp.exp(jnp.where(causal, col - a_cum_r[h:h + 1, :], -jnp.inf))
            M = cb * L * dt_r[h:h + 1, :]
            y_h = _dot(M, x) + cs * decay_c[:, h:h + 1]
            xw_h = x * w_c[:, h:h + 1]
            dec_h = decay_tot[:, h:h + 1]
            if h == w0:
                y, xw, dec = y_h, xw_h, dec_h
            else:
                mine = (lane >= (h - w0) * P) & (lane < (h - w0 + 1) * P)
                y = jnp.where(mine, y_h, y)
                xw = jnp.where(mine, xw_h, xw)
                dec = jnp.where(mine, dec_h, dec)
        state[:, lanes] = s * dec + _dot(Bm, xw, ((0,), (0,)))  # (N, W)
        y_ref[0, :, lanes] = (y + x * D_ref[:, lanes]).astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def emit_state():
        st_ref[0] = state[...].T.astype(st_ref.dtype)


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
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    hb = head_block(H, G, P, N, chunk, x.dtype.itemsize)
    per_group = H // G // hb

    x2 = x.reshape(B, S, H * P)
    dtT = dt.transpose(0, 2, 1)  # (B, H, S): a block's heads on rows
    A2 = A.astype(jnp.float32).reshape(H, 1)
    D2 = jnp.repeat(D.astype(jnp.float32), P).reshape(1, H * P)
    B2 = Bm.reshape(B, S, G * N)
    C2 = Cm.reshape(B, S, G * N)

    kernel = functools.partial(
        _ssd_kernel, chunk=chunk, heads=hb, head_dim=P, n_chunks=nc
    )
    group = pl.BlockSpec((1, chunk, N), lambda b, i, c: (b, c, i // per_group))
    lanes = pl.BlockSpec((1, chunk, hb * P), lambda b, i, c: (b, c, i))
    y, st = pl.pallas_call(
        kernel,
        grid=(B, H // hb, nc),
        in_specs=[
            lanes,
            pl.BlockSpec((1, hb, chunk), lambda b, i, c: (b, i, c)),
            pl.BlockSpec((hb, 1), lambda b, i, c: (i, 0)),
            group,
            group,
            pl.BlockSpec((1, hb * P), lambda b, i, c: (0, i)),
        ],
        out_specs=[
            lanes,
            pl.BlockSpec((1, hb * P, N), lambda b, i, c: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * P), x.dtype),
            jax.ShapeDtypeStruct((B, H * P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, hb * P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        # Names the HLO instruction (ssd_scan.<n>) by which a device trace
        # finds the kernel, whatever function calls it.
        name="ssd_scan",
    )(x2, dtT, A2, B2, C2, D2)
    return y.reshape(B, S, H, P), st.reshape(B, H, P, N)
