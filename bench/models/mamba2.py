"""Mamba-2 (arXiv:2405.21060) as a configuration file of ``bench/configs`` states it.

The forward pass, the loss, the weights made from a seed and the operation
counts are the benchmark's own.  The reference parts import nothing of the
program; ``arch_config`` alone translates the file into the program's
``ArchConfig``, and ``init_params`` lays the weights out in the program's
parameter tree so that the trainer can be handed them.

Block (Mamba-2, the "ssd_minimal" listing of the paper for the scan):
``x + out_proj(RMSNormGated(SSD(conv(in_proj(RMSNorm(x)))), z))``, tied
embedding and head, every activation in float32.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference import rms_norm, mean_cross_entropy

#: Leaves the program keeps in float32 whatever the configured dtype.
F32_LEAVES = ("A_log", "D", "dt_bias")

#: Keys that cut a configuration to a size the CPU trains in seconds (tests only).
SMOKE = dict(d_model=64, n_layer=2, vocab_size=500, d_state=16, headdim=16, chunk_size=16)


def dims(conf: Dict) -> Dict[str, int]:
    d = conf["d_model"]
    di = conf["expand"] * d
    pad = conf["pad_vocab_size_multiple"]
    return {
        "d": d,
        "di": di,
        "H": di // conf["headdim"],
        "P": conf["headdim"],
        "G": conf["ngroups"],
        "N": conf["d_state"],
        "K": conf["d_conv"],
        "Q": conf["chunk_size"],
        "L": conf["n_layer"],
        "V": -(-conf["vocab_size"] // pad) * pad,
    }


def token_vocab(conf: Dict) -> int:
    """Token ids are drawn below this (the padding rows are never a token)."""
    return conf["vocab_size"]


def arch_config(conf: Dict):
    """The program's configuration of this model."""
    from repro.models.config import ArchConfig

    D = dims(conf)
    return ArchConfig(
        name=conf["name"],
        family="ssm",
        n_layers=D["L"],
        d_model=D["d"],
        n_heads=0,
        d_ff=0,
        vocab=D["V"],
        period=("ssm",),
        mlp_pattern=("none",),
        ssm_state=D["N"],
        ssm_head_dim=D["P"],
        ssm_expand=conf["expand"],
        ssm_groups=D["G"],
        ssm_conv=D["K"],
        ssm_chunk=D["Q"],
        tie_embeddings=conf["tie_embeddings"],
        norm_eps=conf["norm_eps"],
        dtype=conf["train"]["param_dtype"],
        use_pallas=conf["use_pallas"],
    )


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def init_params(key, conf: Dict) -> Dict:
    """Seeded weights in the program's tree layout and storage dtypes."""
    D = dims(conf)
    d, di, H, G, N, K, L, V = (D[k] for k in "d di H G N K L V".split())
    dt = jnp.dtype(conf["train"]["param_dtype"])
    conv_dim = di + 2 * G * N
    ks = jax.random.split(key, 7)

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    dt_min, dt_max = 1e-3, 1e-1
    step = jnp.exp(
        jax.random.uniform(ks[5], (L, H)) * (math.log(dt_max) - math.log(dt_min))
        + math.log(dt_min)
    )
    mixer = {
        "in_proj": normal(ks[1], (L, d, 2 * di + 2 * G * N + H), d ** -0.5),
        "conv_w": jax.random.uniform(ks[2], (L, K, conv_dim), jnp.float32, -0.5, 0.5).astype(dt),
        "conv_b": jax.random.uniform(ks[3], (L, conv_dim), jnp.float32, -0.5, 0.5).astype(dt),
        "A_log": jnp.log(jax.random.uniform(ks[4], (L, H), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((L, H), jnp.float32),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1(step)
        "gate_norm": jnp.ones((L, di), dt),
        "out_proj": normal(ks[6], (L, di, d), di ** -0.5),
    }
    return {
        "embed": normal(ks[0], (V, d), 0.02),
        "final_norm": jnp.ones((d,), dt),
        "stack": {"pos0": {"norm1": jnp.ones((L, d), dt), "mixer": mixer}},
    }


# ---------------------------------------------------------------------------
# The reference forward pass (float32)
# ---------------------------------------------------------------------------
def _segsum(x):
    """``out[..., i, j] = sum(x[..., j+1 : i+1])`` below the diagonal, -inf above."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -jnp.inf)


def ssd(x, dt, A, B, C, D, chunk: int):
    """The SSD scan: x (b,l,h,p), dt (b,l,h), A (h,), B/C (b,l,g,n), D (h,)."""
    b, l, h, p = x.shape
    g = B.shape[2]
    B = jnp.repeat(B, h // g, axis=2)
    C = jnp.repeat(C, h // g, axis=2)
    c = l // chunk
    X = (x * dt[..., None]).reshape(b, c, chunk, h, p)
    Bc = B.reshape(b, c, chunk, h, -1)
    Cc = C.reshape(b, c, chunk, h, -1)
    Ac = jnp.moveaxis((dt * A).reshape(b, c, chunk, h), -1, 1)  # (b,h,c,l)
    A_cs = jnp.cumsum(Ac, axis=-1)
    Lmat = jnp.exp(_segsum(Ac))
    hp = jax.lax.Precision.HIGHEST
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", Cc, Bc, Lmat, X, precision=hp)
    decay = jnp.exp(A_cs[..., -1:] - A_cs)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bc, decay, X, precision=hp)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states, precision=hp)[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Cc, states, jnp.exp(A_cs), precision=hp)
    return (y_diag + y_off).reshape(b, l, h, p) + x * D[None, None, :, None]


def _mixer(p, h, D, eps, mm):
    b, l, _ = h.shape
    di, G, N, H, P = D["di"], D["G"], D["N"], D["H"], D["P"]
    zxbcdt = mm("bld,de->ble", h, p["in_proj"])
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    K = p["conv_w"].shape[0]
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, i : i + l] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xs, B, C = jnp.split(jax.nn.silu(conv), [di, di + G * N], axis=-1)
    y = ssd(
        xs.reshape(b, l, H, P),
        jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]),
        B.reshape(b, l, G, N),
        C.reshape(b, l, G, N),
        p["D"],
        D["Q"],
    )
    y = rms_norm(y.reshape(b, l, di) * jax.nn.silu(z), p["gate_norm"], eps)
    return mm("ble,ed->bld", y, p["out_proj"])


def loss(params, tokens, labels, conf: Dict, mm):
    """Mean next-token cross entropy, float32 throughout, one layer at a time."""
    D = dims(conf)
    eps = conf["norm_eps"]

    def layer(x, p):
        return x + _mixer(p["mixer"], rms_norm(x, p["norm1"], eps), D, eps, mm), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), params["embed"][tokens], params["stack"]["pos0"])
    x = rms_norm(x, params["final_norm"], eps)
    return mean_cross_entropy(x, params["embed"].T, labels, mm)


# ---------------------------------------------------------------------------
# Operation counts (no recompute)
# ---------------------------------------------------------------------------
def ssd_flops_per_token(D: Dict[str, int]) -> int:
    """Forward SSD matmul work per token and layer, chunked algorithm:
    ``C B^T`` once per group, then per head the intra-chunk output, the
    inter-chunk output and the state update."""
    Q, N, P, G, H = D["Q"], D["N"], D["P"], D["G"], D["H"]
    return G * 2 * Q * N + H * (2 * Q * P + 2 * N * P + 2 * N * P)


def train_flops_per_token(conf: Dict, seq_len: int) -> float:
    """Forward and backward (3x forward) model operations per token: the
    projections, the depthwise conv, the SSD scan and the tied head.  The
    embedding lookup is a gather and counts nothing."""
    D = dims(conf)
    d, di, G, N, H, K, L, V = (D[k] for k in "d di G N H K L V".split())
    proj = d * (2 * di + 2 * G * N + H) + di * d
    conv = K * (di + 2 * G * N)
    forward = L * (2 * proj + 2 * conv + ssd_flops_per_token(D)) + 2 * d * V
    return 3.0 * forward


def ssd_kernel_cost(conf: Dict, batch: int, seq_len: int):
    """(operations, bytes) one call of the SSD forward kernel needs: the
    scan's matmul work, and each input read and each output written once
    (x, B, C and y in the configured dtype, dt and the final state in f32)."""
    D = dims(conf)
    H, P, G, N = D["H"], D["P"], D["G"], D["N"]
    act = jnp.dtype(conf["train"]["param_dtype"]).itemsize
    tokens = batch * seq_len
    flops = tokens * ssd_flops_per_token(D)
    nbytes = tokens * (2 * H * P * act + H * 4 + 2 * G * N * act) + batch * H * P * N * 4
    return float(flops), float(nbytes)
