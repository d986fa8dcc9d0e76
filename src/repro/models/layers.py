"""Primitive layers: norms, RoPE, GQA attention (flash-style chunked), MLPs.

Everything is a pure function ``f(params, x, cfg, ...)`` over plain dict
pytrees — no framework.  Matmuls accumulate in fp32 (``preferred_element_type``)
and activations stay in the config dtype, which is what the MXU wants.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (S,) or broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AttnMask:
    causal: bool
    window: Optional[int] = None  # keys with qpos - kpos >= window are masked
    kv_len: Optional[jax.Array] = None  # valid KV prefix length (decode padding)


def _mask_block(
    qpos: jax.Array, kpos: jax.Array, m: AttnMask
) -> jax.Array:
    """Boolean (…, Sq, Sk) mask block from absolute positions."""
    ok = jnp.ones((qpos.shape[-1], kpos.shape[-1]), dtype=bool)
    if m.causal:
        ok &= kpos[None, :] <= qpos[:, None]
    if m.window is not None:
        ok &= kpos[None, :] > (qpos[:, None] - m.window)
    if m.kv_len is not None:
        # kv_len broadcasts per batch: (B, 1, 1) vs (Sq, Sk)
        ok = ok[None] & (kpos[None, None, :] < m.kv_len[:, None, None])
    return ok


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: AttnMask,
    *,
    chunk: int = 1024,
    q_offset: int = 0,
) -> jax.Array:
    """Flash-style attention: scan over KV chunks with an online softmax.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) with H = KV * G (GQA).
    Never materializes the (Sq, Sk) score matrix — peak live memory is
    O(Sq * chunk), which is what makes prefill_32k lowerable.  This is the
    XLA reference path; the Pallas kernel (kernels/flash_attention.py) is
    the TPU-optimized equivalent of this same computation.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = hd ** -0.5
    qg = (q * scale).reshape(B, Sq, KV, G, hd)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, KV, hd)
    vc = v.reshape(B, n_chunks, chunk, KV, hd)
    qpos = q_offset + jnp.arange(Sq)

    def body(carry, xs):
        m_i, l_i, acc = carry
        j, k_j, v_j = xs
        kpos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum(
            "bqkgh,bckh->bkgqc", qg, k_j, preferred_element_type=jnp.float32
        )  # (B, KV, G, Sq, chunk)
        ok = _mask_block(qpos, kpos, mask)
        valid = kpos < Sk  # exclude right padding
        ok = ok & valid[..., None, :] if ok.ndim == 3 else ok & valid[None, :]
        # broadcast mask to (B, KV, G, Sq, chunk)
        if ok.ndim == 2:
            okb = ok[None, None, None]
        else:  # (B, Sq, chunk) from kv_len masking
            okb = ok[:, None, None]
        s = jnp.where(okb, s, -jnp.inf)
        m_new = jnp.maximum(m_i, s.max(axis=-1))
        # Rows with no valid key yet keep m=-inf; guard exp(-inf - -inf).
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(okb, p, 0.0)
        alpha = jnp.where(jnp.isneginf(m_i), 0.0, jnp.exp(m_i - m_safe))
        l_new = l_i * alpha + p.sum(axis=-1)
        # p is consumed by an MXU matmul: store it in the model dtype (the
        # statistics m/l and the accumulator stay f32) — this is what the
        # Pallas kernel does on TPU, and it halves the dominant HBM stream
        # of the 32k-context cells (exp-weight blocks).
        pv = jnp.einsum(
            "bkgqc,bckh->bkgqh", p.astype(v_j.dtype), v_j,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), dtype=jnp.float32)
    acc0 = jnp.zeros((B, KV, G, Sq, hd), dtype=jnp.float32)
    xs = (jnp.arange(n_chunks), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0))
    # Checkpointed: the backward keeps one chunk's scores at a time too.
    (m_f, l_f, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, acc0), xs)
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]  # (B, KV, G, Sq, hd)
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, hd).astype(q.dtype)


def plain_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mask: AttnMask, q_offset: int = 0
) -> jax.Array:
    """Direct softmax attention (oracle for tests; decode fast path)."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    qg = (q * hd ** -0.5).reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k, preferred_element_type=jnp.float32)
    ok = _mask_block(q_offset + jnp.arange(Sq), jnp.arange(Sk), mask)
    okb = ok[None, None, None] if ok.ndim == 2 else ok[:, None, None]
    s = jnp.where(okb, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    o = jnp.einsum("bkgqs,bskh->bkgqh", p, v, preferred_element_type=jnp.float32)
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd).astype(q.dtype)


#: Query and key block of the Pallas flash-attention forward: at 8k tokens
#: a 128-block grid has 64x64 steps a head, whose per-step cost outweighs
#: their work; a 512 block keeps the grid at 16x16 (kernels/flash_attention.py
#: clamps it to shorter sequences).
FLASH_BLOCK = 512


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_trainable(q, k, v, causal: bool, window: Optional[int], chunk: int):
    """The Pallas flash-attention kernel (kernels/flash_attention.py) with a
    backward pass.  A ``pallas_call`` has no transpose rule, so the forward
    runs the kernel and the backward differentiates ``chunked_attention``,
    the XLA form of the same attention, recomputed from the saved inputs
    one block of ``chunk`` queries at a time (each block against the keys
    it can see), so that no block's residuals outgrow a chunk's."""
    from repro.kernels.ops import flash_attention  # lazy: no cycle

    return flash_attention(
        q, k, v, causal=causal, window=window, block_q=FLASH_BLOCK, block_k=FLASH_BLOCK
    )


def _flash_fwd(q, k, v, causal, window, chunk):
    return flash_attention_trainable(q, k, v, causal, window, chunk), (q, k, v)


def _flash_bwd(causal, window, chunk, inputs, g):
    q, k, v = inputs
    S = q.shape[1]
    block = min(chunk, S)
    mask = AttnMask(causal=causal, window=window)
    dq, dk, dv = [], jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)
    for start in range(0, S, block):
        stop = min(start + block, S)
        # A causal block sees the keys up to its last query; slicing the
        # rest away changes nothing the mask would not drop.
        seen = stop if causal else k.shape[1]

        def attend(q_blk, k_seen, v_seen, start=start):
            return chunked_attention(q_blk, k_seen, v_seen, mask, chunk=chunk, q_offset=start)

        _, vjp = jax.vjp(attend, q[:, start:stop], k[:, :seen], v[:, :seen])
        dq_b, dk_b, dv_b = vjp(g[:, start:stop])
        dq.append(dq_b)
        dk = dk.at[:, :seen].add(dk_b.astype(jnp.float32))
        dv = dv.at[:, :seen].add(dv_b.astype(jnp.float32))
    return jnp.concatenate(dq, axis=1), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention_trainable.defvjp(_flash_fwd, _flash_bwd)


def attention_block(
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    *,
    positions: jax.Array,
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    kv_len: Optional[jax.Array] = None,
    use_chunked: bool = True,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Full attention sub-layer: qkv proj, rope, SDPA, out proj.

    Training/prefill: ``kv_cache=None`` — attends within ``x``.
    Decode: ``kv_cache=(K, V)`` of shape (B, S_max, KV, hd); the new token's
    K/V are written at ``cache_pos`` and attention runs over the cache.
    """
    B, S, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    q = jnp.einsum("bsd,dq->bsq", x, params["wq"], preferred_element_type=jnp.float32)
    k = jnp.einsum("bsd,dq->bsq", x, params["wk"], preferred_element_type=jnp.float32)
    v = jnp.einsum("bsd,dq->bsq", x, params["wv"], preferred_element_type=jnp.float32)
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.astype(dt).reshape(B, S, H, hd)
    k = k.astype(dt).reshape(B, S, KV, hd)
    v = v.astype(dt).reshape(B, S, KV, hd)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if kv_cache is None:
        mask = AttnMask(causal=cfg.causal, window=cfg.window)
        if cfg.use_pallas:
            out = flash_attention_trainable(q, k, v, cfg.causal, cfg.window, cfg.attn_chunk)
        elif use_chunked and S > cfg.attn_chunk:
            out = chunked_attention(q, k, v, mask, chunk=cfg.attn_chunk)
        else:
            out = plain_attention(q, k, v, mask)
    else:
        K, V = kv_cache
        assert cache_pos is not None
        K = jax.lax.dynamic_update_slice_in_dim(K, k, cache_pos, axis=1)
        V = jax.lax.dynamic_update_slice_in_dim(V, v, cache_pos, axis=1)
        new_cache = (K, V)
        q_off = cache_pos  # query absolute position == its cache slot
        mask = AttnMask(causal=cfg.causal, window=cfg.window, kv_len=kv_len)
        out = plain_attention(q, K, V, mask, q_offset=q_off)
    y = jnp.einsum(
        "bsq,qd->bsd", out.reshape(B, S, H * hd), params["wo"],
        preferred_element_type=jnp.float32,
    )
    return y.astype(dt), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def activation(u: jax.Array, act: str) -> jax.Array:
    """The nonlinearity of a 2-matrix MLP: gelu, or relu2 (squared ReLU)."""
    if act == "relu2":
        return jnp.square(jax.nn.relu(u))
    return jax.nn.gelu(u)


def mlp_block(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    dt = x.dtype
    if cfg.mlp_act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"], preferred_element_type=jnp.float32)
        u = jnp.einsum("bsd,df->bsf", x, params["w_up"], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(dt)
    else:  # gelu (encoder stacks) or relu2: 2-matrix MLP
        u = jnp.einsum("bsd,df->bsf", x, params["w_up"], preferred_element_type=jnp.float32)
        h = activation(u, cfg.mlp_act).astype(dt)
    y = jnp.einsum("bsf,fd->bsd", h, params["w_down"], preferred_element_type=jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# Parameter initialization helpers
# ---------------------------------------------------------------------------
def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype, fan_in: int) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)


def attn_param_shapes(cfg: ArchConfig) -> dict:
    q = cfg.n_heads * cfg.head_dim
    kv = cfg.n_kv_heads * cfg.head_dim
    d = cfg.d_model
    shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    if cfg.qkv_bias:
        shapes.update({"bq": (q,), "bk": (kv,), "bv": (kv,)})
    return shapes


def mlp_param_shapes(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_act == "swiglu":
        return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return {"w_up": (d, f), "w_down": (f, d)}
