"""Mamba-2 (SSD — state-space duality) token mixer.

Three equivalent computations of the same selective-SSM recurrence

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * B_t x_t^T          (state: H, P, N)
    y_t = C_t . S_t + D_h * x_t

are provided:

  * ``ssd_reference``  — O(S^2) sequential scan oracle (tests only);
  * ``ssd_chunked``    — the paper's chunked algorithm: quadratic *within*
    length-Q chunks (MXU-friendly matmuls) + a linear inter-chunk state
    recurrence via ``lax.scan``.  This is the training/prefill path and the
    shape the Pallas kernel (kernels/ssd.py) tiles;
  * ``ssd_decode_step``— O(1)/token recurrent update used by the serving
    engine (this is what makes long_500k decode runnable for SSM/hybrid).

Shapes follow the Mamba-2 paper: x (B,S,H,P), dt (B,S,H), A (H,) scalar
per head, B/C (B,S,G,N) with heads grouped G | H.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import rms_norm


def ssd_reference(x, dt, A, Bm, Cm, D) -> jax.Array:
    """Sequential scan over time — the oracle. All args f32.

    x: (B,S,H,P) dt: (B,S,H) A: (H,) Bm/Cm: (B,S,G,N) D: (H,)
    """
    Bb, S, H, Pd = x.shape
    G = Bm.shape[2]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)  # (B,S,H,N)
    Ch = jnp.repeat(Cm, rep, axis=2)

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp  # (B,H,P),(B,H),(B,H,N),(B,H,N)
        decay = jnp.exp(dt_t * A)  # (B,H)
        state = state * decay[..., None, None] + jnp.einsum(
            "bh,bhn,bhp->bhpn", dt_t, B_t, x_t
        )
        y = jnp.einsum("bhpn,bhn->bhp", state, C_t)
        return state, y

    s0 = jnp.zeros((Bb, H, Pd, Bm.shape[-1]), jnp.float32)
    xs = (
        jnp.moveaxis(x, 1, 0),
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(Bh, 1, 0),
        jnp.moveaxis(Ch, 1, 0),
    )
    _, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (B,S,H,P)
    return y + x * D[None, None, :, None]


def ssd_chunked(x, dt, A, Bm, Cm, D, *, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD: returns (y (B,S,H,P) f32, final_state (B,H,P,N) f32).

    Within each chunk the computation is a masked 'attention' matmul
    (C_i . B_j) * exp(a_i - a_j) * dt_j — pure MXU work; across chunks a
    (H,P,N) state is carried by a scan of length S/Q.

    The intra-chunk quadratic work happens INSIDE the scan body (checkpointed)
    so peak live memory is O(B·Q·Q·H) for ONE chunk — materializing all
    chunks at once costs B·S·Q·H·f32 per temporary, which blows past HBM for
    the train_4k cells.  This is also the structure the Pallas kernel tiles
    (grid over chunks, state carried in VMEM).
    """
    Bb, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    xc = x.reshape(Bb, nc, chunk, H, Pd)
    dtc = dt.reshape(Bb, nc, chunk, H)
    Bc = Bm.reshape(Bb, nc, chunk, G, N)
    Cc = Cm.reshape(Bb, nc, chunk, G, N)
    ii = jnp.arange(chunk)
    causal = (ii[:, None] >= ii[None, :])[None, :, :, None]  # (1,Qi,Qj,1)

    def body(S_prev, inp):
        x_j, dt_j, B_j, C_j = inp  # (B,Q,H,P),(B,Q,H),(B,Q,G,N),(B,Q,G,N)
        a = dt_j * A[None, None, :]  # (B,Q,H)
        a_cum = jnp.cumsum(a, axis=1)
        a_total = a_cum[:, -1, :]  # (B,H)
        # intra-chunk: L[i,j] = exp(a_i - a_j) (i>=j); scores (C_i . B_j)
        seg = a_cum[:, :, None, :] - a_cum[:, None, :, :]  # (B,Qi,Qj,H)
        # Mask before exp: above the diagonal seg is a positive sum of up to
        # Q decays, exp overflows to inf, and where's backward then makes
        # 0 * inf = NaN gradients.
        L = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum(
            "bign,bjgn->bijg", C_j, B_j, preferred_element_type=jnp.float32
        )  # (B,Qi,Qj,G) — inputs may be bf16; accumulate f32
        cb = jnp.repeat(cb, rep, axis=-1)
        M = cb * L * dt_j[:, None, :, :].astype(jnp.float32)
        y = jnp.einsum("bijh,bjhp->bihp", M, x_j, preferred_element_type=jnp.float32)
        # inter-chunk: y_i += exp(a_cum[i]) C_i . S_entering
        Ch = jnp.repeat(C_j, rep, axis=2)  # (B,Q,H,N)
        y = y + jnp.einsum(
            "bqhn,bhpn->bqhp", Ch, S_prev, preferred_element_type=jnp.float32
        ) * jnp.exp(a_cum)[..., None]
        # state update: S_new = exp(a_total) S_prev + sum_j exp(a_total-a_j) dt_j B_j x_j
        w = jnp.exp(a_total[:, None, :] - a_cum) * dt_j.astype(jnp.float32)  # (B,Q,H)
        Bh = jnp.repeat(B_j, rep, axis=2)  # (B,Q,H,N)
        cs = jnp.einsum(
            "bqh,bqhn,bqhp->bhpn", w, Bh, x_j, preferred_element_type=jnp.float32
        )
        S_new = S_prev * jnp.exp(a_total)[..., None, None] + cs
        return S_new, y.astype(x_j.dtype)  # stream y in the model dtype

    s0 = jnp.zeros((Bb, H, Pd, N), jnp.float32)
    final, ys = jax.lax.scan(
        jax.checkpoint(body),
        s0,
        (
            jnp.moveaxis(xc, 1, 0),
            jnp.moveaxis(dtc, 1, 0),
            jnp.moveaxis(Bc, 1, 0),
            jnp.moveaxis(Cc, 1, 0),
        ),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(Bb, S, H, Pd)
    return y + x * D[None, None, :, None], final


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_pallas(x, dt, A, Bm, Cm, D, chunk: int):
    """The Pallas SSD kernel (kernels/ssd.py) with a backward pass.

    A ``pallas_call`` has no transpose rule, so the forward runs the kernel
    and the backward differentiates ``ssd_chunked``, the XLA form of the
    same recurrence, recomputed from the saved inputs.
    """
    from repro.kernels.ops import ssd_scan  # lazy: no cycle

    return ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk)


def _ssd_pallas_fwd(x, dt, A, Bm, Cm, D, chunk):
    return ssd_pallas(x, dt, A, Bm, Cm, D, chunk), (x, dt, A, Bm, Cm, D)


def _ssd_pallas_bwd(chunk, inputs, grads):
    _, vjp = jax.vjp(lambda *a: ssd_chunked(*a, chunk=chunk), *inputs)
    g_y, g_state = grads
    return vjp((g_y.astype(jnp.float32), g_state))


ssd_pallas.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)


def ssd_decode_step(state, x, dt, A, Bm, Cm, D):
    """One-token recurrence. state (B,H,P,N); x (B,H,P); dt (B,H);
    Bm/Cm (B,G,N). Returns (y (B,H,P), new_state)."""
    H = x.shape[1]
    rep = H // Bm.shape[1]
    Bh = jnp.repeat(Bm, rep, axis=1)  # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1)
    decay = jnp.exp(dt * A)  # (B,H)
    state = state * decay[..., None, None] + jnp.einsum("bh,bhn,bhp->bhpn", dt, Bh, x)
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch) + x * D[None, :, None]
    return y, state


# ---------------------------------------------------------------------------
# The full Mamba-2 block (in_proj -> conv -> SSD -> gated norm -> out_proj)
# ---------------------------------------------------------------------------
def gated_rms_norm(x: jax.Array, scale: jax.Array, eps: float, groups: int) -> jax.Array:
    """RMSNorm of the gated SSD output over each B/C group's channels
    (mamba_ssm's ``RMSNormGated`` with ``group_size = d_inner / ngroups``).
    One group is the plain RMSNorm over all channels."""
    if groups == 1:
        return rms_norm(x, scale, eps)
    shape = x.shape
    y = x.astype(jnp.float32).reshape(*shape[:-1], groups, shape[-1] // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(shape) * scale.astype(jnp.float32)).astype(x.dtype)


def ssm_param_shapes(cfg: ArchConfig) -> dict:
    d, di = cfg.d_model, cfg.d_inner
    G, N, H = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * G * N
    return {
        "in_proj": (d, 2 * di + 2 * G * N + H),
        "conv_w": (cfg.ssm_conv, conv_dim),
        "conv_b": (conv_dim,),
        "A_log": (H,),
        "D": (H,),
        "dt_bias": (H,),
        "gate_norm": (di,),
        "out_proj": (di, d),
    }


def _split_proj(cfg: ArchConfig, zxbcdt: jax.Array):
    di, G, N, H = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    return jnp.split(zxbcdt, [di, 2 * di, 2 * di + G * N, 2 * di + 2 * G * N], axis=-1)


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. xBC (B,S,C), w (K,C)."""
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xBC.shape[1], :] * w[i][None, None, :] for i in range(K))
    return jax.nn.silu(out + b)


def ssm_block(
    params: dict, x: jax.Array, cfg: ArchConfig, *, state=None
) -> Tuple[jax.Array, object]:
    """Mamba-2 mixer over a full sequence (train/prefill).

    Returns (y (B,S,d), carry) where carry = (ssd_state, conv_tail) for
    handing off to incremental decode.
    """
    Bb, S, d = x.shape
    dt0 = x.dtype
    di, G, N, H, Pd = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum(
        "bsd,de->bse", x, params["in_proj"], preferred_element_type=jnp.float32
    )
    z, xr, Bm, Cm, dt = _split_proj(cfg, zxbcdt)
    # Activation streams (z, x, B, C) live in the model dtype; only the dt
    # path, the decay chain and the SSD state stay f32.
    z = z.astype(dt0)
    xBC = jnp.concatenate([xr, Bm, Cm], axis=-1).astype(dt0)
    xBC = _causal_conv(xBC, params["conv_w"].astype(jnp.float32), params["conv_b"].astype(jnp.float32))
    xBC = xBC.astype(dt0)
    xr, Bm, Cm = jnp.split(xBC, [di, di + G * N], axis=-1)
    dtv = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))  # (B,S,H)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    xh = xr.reshape(Bb, S, H, Pd)
    Bg = Bm.reshape(Bb, S, G, N)
    Cg = Cm.reshape(Bb, S, G, N)
    chunk = min(cfg.ssm_chunk, S)
    if S % chunk:  # pad to a chunk multiple (prefill of odd lengths)
        padn = chunk - S % chunk
        xh = jnp.pad(xh, ((0, 0), (0, padn), (0, 0), (0, 0)))
        dtv = jnp.pad(dtv, ((0, 0), (0, padn), (0, 0)))
        Bg = jnp.pad(Bg, ((0, 0), (0, padn), (0, 0), (0, 0)))
        Cg = jnp.pad(Cg, ((0, 0), (0, padn), (0, 0), (0, 0)))
    # Stream x/B/C through the SSD in the model dtype (the decay chain a_cum
    # and the carried state stay f32 inside the scan body) — halves the
    # dominant HBM stream of the SSM cells and matches what the Pallas
    # kernel consumes on TPU.
    xh, Bg, Cg = xh.astype(dt0), Bg.astype(dt0), Cg.astype(dt0)
    if cfg.use_pallas:
        y, ssd_state = ssd_pallas(
            xh, dtv, A, Bg, Cg, params["D"].astype(jnp.float32), chunk
        )
        y = y.astype(jnp.float32)
    else:
        y, ssd_state = ssd_chunked(
            xh, dtv, A, Bg, Cg, params["D"].astype(jnp.float32), chunk=chunk
        )
    y = y[:, :S].reshape(Bb, S, di).astype(dt0)
    y = gated_rms_norm(y * jax.nn.silu(z), params["gate_norm"], cfg.norm_eps, G)
    out = jnp.einsum(
        "bse,ed->bsd", y, params["out_proj"], preferred_element_type=jnp.float32
    ).astype(dt0)
    # conv tail: last (K-1) *pre-conv* channel values, for incremental decode
    K = cfg.ssm_conv
    zxbcdt_tail = zxbcdt[:, -(K - 1) :, :]
    _, xr_t, Bm_t, Cm_t, _ = _split_proj(cfg, zxbcdt_tail)
    conv_tail = jnp.concatenate([xr_t, Bm_t, Cm_t], axis=-1)  # (B,K-1,conv_dim)
    return out, (ssd_state, conv_tail)


def ssm_block_decode(
    params: dict, x: jax.Array, cfg: ArchConfig, carry
) -> Tuple[jax.Array, object]:
    """One-token Mamba-2 step. x (B,1,d); carry (ssd_state, conv_tail)."""
    Bb, S, d = x.shape
    assert S == 1
    dt0 = x.dtype
    di, G, N, H, Pd = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    ssd_state, conv_tail = carry
    zxbcdt = jnp.einsum(
        "bsd,de->bse", x, params["in_proj"], preferred_element_type=jnp.float32
    )
    z, xr, Bm, Cm, dt = _split_proj(cfg, zxbcdt)
    xBC_new = jnp.concatenate([xr, Bm, Cm], axis=-1)  # (B,1,conv_dim)
    window = jnp.concatenate([conv_tail, xBC_new], axis=1)  # (B,K,conv_dim)
    w = params["conv_w"].astype(jnp.float32)
    out = (window * w[None, :, :]).sum(axis=1, keepdims=True)
    xBC = jax.nn.silu(out + params["conv_b"].astype(jnp.float32))
    xr2, Bm2, Cm2 = jnp.split(xBC[:, 0], [di, di + G * N], axis=-1)
    dtv = jax.nn.softplus(dt[:, 0] + params["dt_bias"].astype(jnp.float32))  # (B,H)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    y, ssd_state = ssd_decode_step(
        ssd_state,
        xr2.reshape(Bb, H, Pd),
        dtv,
        A,
        Bm2.reshape(Bb, G, N),
        Cm2.reshape(Bb, G, N),
        params["D"].astype(jnp.float32),
    )
    y = y.reshape(Bb, 1, di)
    y = gated_rms_norm((y * jax.nn.silu(z)).astype(dt0), params["gate_norm"], cfg.norm_eps, G)
    out = jnp.einsum(
        "bse,ed->bsd", y, params["out_proj"], preferred_element_type=jnp.float32
    ).astype(dt0)
    new_tail = window[:, 1:, :]
    return out, (ssd_state, new_tail)


def ssm_empty_carry(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    di, G, N, H, Pd = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    conv_dim = di + 2 * G * N
    return (
        jnp.zeros((batch, H, Pd, N), jnp.float32),
        jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), jnp.float32),
    )
