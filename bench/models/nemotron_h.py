"""Nemotron-H (``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B) as a configuration file of
``bench/configs`` states it, cut to the chip's share of a stated deployment.

Blocks follow ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` a mixture of experts,
``*`` grouped-query attention, each ``x + mixer(RMSNorm(x))``.  The program's period holds one
token mixer and one channel mixer a position, so an ``E`` joins the block before it:
``MEMEM*E`` is (M, E), (M, E), (M, -), (*, E).

* Mamba-2: in_proj to (z, x, B, C, dt) with ``mamba_num_heads`` x ``mamba_head_dim`` channels
  (not expand x d_model), causal depthwise conv with bias, the SSD scan (``mamba2.ssd``, one
  B/C group's heads at a time), then ``RMSNorm(y * silu(z))`` taken over each of the
  ``n_groups`` B/C groups, out_proj.
* MoE: sigmoid scores over all ``n_routed_experts * deployment.expert_parallel`` experts in
  float32; a selection bias (``e_score_correction_bias``) is added for the top-k choice only;
  the chosen scores are normalised to sum 1 and scaled by ``routed_scaling_factor``.  The
  weights are made with the bias that balances the experts' loads on a seeded batch
  (``balance_router``), as the release's own bias update keeps them.  Experts are
  ``down(relu(up(x))^2)``; this chip holds ``n_routed_experts`` of them, the first share, and
  computes their part; one shared expert of ``moe_shared_expert_intermediate_size`` every token.
* Attention: 32 query and 2 KV heads of 128, causal, no rotary embedding, no bias.
* Untied head over the vocabulary slice; every activation of the reference in float32.

The reference imports nothing of the program; ``arch_config`` alone translates the file into
the program's ``ArchConfig``, and ``init_params`` lays the weights out in its parameter tree.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.models.mamba2 import ssd, ssd_flops_per_token
from bench.reference import HIGHEST, matmul, mean_cross_entropy, rms_norm

#: Leaves the program keeps in float32 whatever the configured dtype.
F32_LEAVES = ("A_log", "D", "dt_bias", "router_bias")

#: Keys that cut a configuration to a size the CPU trains in seconds (tests only).
SMOKE = dict(
    hidden_size=64, mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
    chunk_size=16, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48, n_routed_experts=4,
    num_experts_per_tok=3, vocab_size=512,
)

#: Query positions of the reference's attention a block, each block checkpointed.
ATTN_BLOCK = 256

#: The selection bias's balancing: sign steps, the first step's size, and its
#: shrink a step (the steps sum to ~3, more than sigmoid scores can differ by).
BALANCE_STEPS, BALANCE_STEP0, BALANCE_DECAY = 256, 0.1, 0.97


def layout(pattern: str) -> List[Tuple[str, str]]:
    """(token mixer, channel mixer) of each period position from the block pattern."""
    out: List[List[str]] = []
    for block in pattern:
        if block in "M*":
            out.append(["ssm" if block == "M" else "attn", "none"])
        elif block == "E" and out and out[-1][1] == "none":
            out[-1][1] = "moe"
        else:
            raise ValueError(f"pattern {pattern!r}: an E must follow an M or a *")
    return [tuple(p) for p in out]


def dims(conf: Dict) -> Dict[str, int]:
    H, P, G = conf["mamba_num_heads"], conf["mamba_head_dim"], conf["n_groups"]
    pattern = conf["hybrid_override_pattern"]
    if len(pattern) != conf["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers disagree")
    held = conf["n_routed_experts"]
    return {
        "d": conf["hidden_size"],
        "di": H * P,
        "H": H,
        "P": P,
        "G": G,
        "N": conf["ssm_state_size"],
        "K": conf["conv_kernel"],
        "Q": conf["chunk_size"],
        "QH": conf["num_attention_heads"],
        "KVH": conf["num_key_value_heads"],
        "hd": conf["head_dim"],
        "f": conf["moe_intermediate_size"],
        "fs": conf["moe_shared_expert_intermediate_size"] * conf["n_shared_experts"],
        "held": held,
        "E": held * conf["deployment"]["expert_parallel"],
        "k": conf["num_experts_per_tok"],
        "V": conf["vocab_size"],
        "ssm_layers": pattern.count("M"),
        "moe_layers": pattern.count("E"),
        "attn_layers": pattern.count("*"),
    }


def token_vocab(conf: Dict) -> int:
    """Token ids are drawn below this: the vocabulary slice the chip holds."""
    return conf["vocab_size"]


def arch_config(conf: Dict):
    """The program's configuration of this model."""
    from repro.models.config import ArchConfig

    D = dims(conf)
    pos = layout(conf["hybrid_override_pattern"])
    return ArchConfig(
        name=conf["name"],
        family="hybrid",
        n_layers=len(pos),
        d_model=D["d"],
        n_heads=D["QH"],
        n_kv_heads=D["KVH"],
        head_dim=D["hd"],
        d_ff=D["f"],
        vocab=D["V"],
        n_experts=D["E"],
        top_k=D["k"],
        capacity_factor=0.0,
        router="sigmoid_bias",
        routed_scale=conf["routed_scaling_factor"],
        shared_expert_ff=D["fs"],
        experts_held=D["held"],
        qkv_bias=conf["attention_bias"],
        pos_emb="none",
        ssm_state=D["N"],
        ssm_n_heads=D["H"],
        ssm_head_dim=D["P"],
        ssm_groups=D["G"],
        ssm_conv=D["K"],
        ssm_chunk=D["Q"],
        period=tuple(m for m, _ in pos),
        mlp_pattern=tuple(c for _, c in pos),
        mlp_act=conf["mlp_hidden_act"],
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["norm_eps"],
        dtype=conf["train"]["param_dtype"],
        use_pallas=conf["use_pallas"],
    )


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def init_params(key, conf: Dict) -> Dict:
    """Seeded weights in the program's tree layout and storage dtypes (one period)."""
    D = dims(conf)
    d, di, H, G, N, K = (D[k] for k in "d di H G N K".split())
    dt = jnp.dtype(conf["train"]["param_dtype"])
    keys = iter(jax.random.split(key, 64))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dt)

    def proj(*shape):  # N(0, 1/fan_in), one period deep
        return normal((1, *shape), shape[-2] ** -0.5)

    def ones(n):
        return jnp.ones((1, n), dt)

    def mamba():
        conv_dim = di + 2 * G * N
        lo, hi = math.log(conf["time_step_min"]), math.log(conf["time_step_max"])
        step = jnp.exp(jax.random.uniform(next(keys), (1, H)) * (hi - lo) + lo)
        step = jnp.maximum(step, conf["time_step_floor"])
        def uniform(shape):
            return jax.random.uniform(next(keys), shape, jnp.float32, -0.5, 0.5).astype(dt)

        return {
            "in_proj": proj(d, 2 * di + 2 * G * N + H),
            "conv_w": uniform((1, K, conv_dim)),
            "conv_b": uniform((1, conv_dim)),
            "A_log": jnp.log(jax.random.uniform(next(keys), (1, H), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((1, H), jnp.float32),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1(step)
            "gate_norm": ones(di),
            "out_proj": proj(di, d),
        }

    def attention():
        q, kv = D["QH"] * D["hd"], D["KVH"] * D["hd"]
        return {"wq": proj(d, q), "wk": proj(d, kv), "wv": proj(d, kv), "wo": proj(q, d)}

    def moe():
        f, held = D["f"], D["held"]
        return {
            "router": proj(d, D["E"]),
            "w_up": normal((1, held, d, f), d ** -0.5),
            "w_down": normal((1, held, f, d), f ** -0.5),
            "router_bias": jnp.zeros((1, D["E"]), jnp.float32),  # set by balance_router
            "shared_up": proj(d, D["fs"]),
            "shared_down": proj(D["fs"], d),
        }

    stack = {}
    for i, (mixer, mlp) in enumerate(layout(conf["hybrid_override_pattern"])):
        pos = {"norm1": ones(d), "mixer": mamba() if mixer == "ssm" else attention()}
        if mlp == "moe":
            pos.update(norm2=ones(d), mlp=moe())
        stack[f"pos{i}"] = pos
    params = {
        "embed": normal((D["V"], d), 0.02),
        "head": normal((d, D["V"]), d ** -0.5),
        "final_norm": jnp.ones((d,), dt),
        "stack": stack,
    }
    return balance_router(params, next(keys), conf)


def balanced_bias(scores, k: int):
    """A selection bias under which the top ``k`` of ``scores + bias`` give
    each expert near ``T * k / E`` of the ``T`` tokens' choices: the
    aux-loss-free update (b += u * sign(mean load - load), arXiv:2408.15664)
    iterated on one batch with a shrinking step u."""
    T, E = scores.shape
    target = T * k / E

    def step(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        return b + BALANCE_STEP0 * BALANCE_DECAY ** i * jnp.sign(target - load)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step, jnp.zeros((E,), jnp.float32))


def balance_router(params, key, conf: Dict) -> Dict:
    """``params`` with each MoE layer's selection bias balancing the experts
    on one seeded batch of the cell's shape, made as the objects are
    (``bench/payloads.py``: ids below the vocabulary slice, each odd position
    repeating its predecessor), layer after layer in the float32 forward.
    At random weights the tokens' hidden states share most of their
    direction, so with no bias nearly every token picks the same experts."""
    tr = conf["train"]
    rows = jax.random.randint(key, (tr["batch"], tr["seq_len"] + 1), 0, token_vocab(conf))
    tokens = rows.at[:, 1::2].set(rows[:, 0:-1:2])[:, :-1]
    _, stack = _forward(params, tokens, conf, matmul("fp32"), balance=True)
    return dict(params, stack=stack)


# ---------------------------------------------------------------------------
# The reference forward pass (float32)
# ---------------------------------------------------------------------------
def _mamba(p, h, D, eps, mm):
    b, l, _ = h.shape
    di, G, N, H, P = D["di"], D["G"], D["N"], D["H"], D["P"]
    zxbcdt = mm("bld,de->ble", h, p["in_proj"])
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    K = p["conv_w"].shape[0]
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, i : i + l] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xs, B, C = jnp.split(jax.nn.silu(conv), [di, di + G * N], axis=-1)
    rep = H // G

    def group(_, g):  # the heads of one B/C group: the scan is separate per head
        x_g, dt_g, A_g, B_g, C_g, D_g = g
        return None, ssd(x_g, dt_g, A_g, B_g, C_g, D_g, D["Q"])

    by_group = (
        jnp.moveaxis(xs.reshape(b, l, G, rep, P), 2, 0),
        jnp.moveaxis(jax.nn.softplus(dt + p["dt_bias"]).reshape(b, l, G, rep), 2, 0),
        (-jnp.exp(p["A_log"])).reshape(G, rep),
        jnp.moveaxis(B.reshape(b, l, G, 1, N), 2, 0),
        jnp.moveaxis(C.reshape(b, l, G, 1, N), 2, 0),
        p["D"].reshape(G, rep),
    )
    _, y = jax.lax.scan(jax.checkpoint(group), None, by_group)  # (G, b, l, rep, P)
    y = jnp.moveaxis(y, 0, 2)
    gated = (y.reshape(b, l, di) * jax.nn.silu(z)).reshape(b, l, G, di // G)
    y = rms_norm(gated, 1.0, eps).reshape(b, l, di) * p["gate_norm"]  # per-group norm
    return mm("ble,ed->bld", y, p["out_proj"])


def _attention(p, h, D, mm):
    b, l, _ = h.shape
    QH, KVH, hd = D["QH"], D["KVH"], D["hd"]
    q = mm("bld,dq->blq", h, p["wq"]).reshape(b, l, KVH, QH // KVH, hd) * hd ** -0.5
    k = mm("bld,dq->blq", h, p["wk"]).reshape(b, l, KVH, hd)
    v = mm("bld,dq->blq", h, p["wv"]).reshape(b, l, KVH, hd)
    blk = min(ATTN_BLOCK, l)

    def block(_, start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, axis=1)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qb, k, precision=HIGHEST)
        seen = jnp.arange(l)[None, :] <= (start + jnp.arange(blk))[:, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return None, jnp.einsum("bkgqs,bskh->bqkgh", w, v, precision=HIGHEST)

    _, out = jax.lax.scan(jax.checkpoint(block), None, jnp.arange(0, l, blk))
    out = jnp.moveaxis(out, 0, 1).reshape(b, l, QH * hd)  # (b, blocks, blk, ...) in order
    return mm("blq,qd->bld", out, p["wo"])


def _moe(p, h, D, conf, mm):
    """The held experts computed densely for every token, each weighted by its
    combine weight (0 where the token is not routed to it), plus the shared expert."""
    b, l, d = h.shape
    x = h.reshape(b * l, d)
    scores = jax.nn.sigmoid(mm("td,de->te", x, p["router"]))
    _, idx = jax.lax.top_k(scores + p["router_bias"], D["k"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * conf["routed_scaling_factor"]
    combine = jnp.stack([jnp.sum(w * (idx == e), -1) for e in range(D["held"])], -1)

    def expert(y, e):
        w_up, w_down, c = e
        hidden = jnp.square(jax.nn.relu(mm("td,df->tf", x, w_up)))
        return y + mm("tf,fd->td", hidden, w_down) * c[:, None], None

    y, _ = jax.lax.scan(
        jax.checkpoint(expert), jnp.zeros_like(x), (p["w_up"], p["w_down"], combine.T)
    )
    shared = mm("tf,fd->td", jnp.square(jax.nn.relu(mm("td,df->tf", x, p["shared_up"]))),
                p["shared_down"])
    return (y + shared).reshape(b, l, d)


def _forward(params, tokens, conf: Dict, mm, balance: bool = False):
    """The hidden states before the final norm, and the period's weights.
    With ``balance``, each MoE layer's ``router_bias`` is first replaced by
    ``balanced_bias`` of its scores on these tokens (the weights so changed
    are returned; the activations are float32 then, whatever the storage)."""
    D = dims(conf)
    eps = conf["norm_eps"]
    stack = dict(params["stack"])
    x = params["embed"][tokens]
    if balance:
        x = x.astype(jnp.float32)
    for i, (mixer, mlp) in enumerate(layout(conf["hybrid_override_pattern"])):
        p = jax.tree.map(lambda a: a[0], stack[f"pos{i}"])
        if mixer == "ssm":
            mix = jax.checkpoint(lambda p, h: _mamba(p, h, D, eps, mm))
        else:
            mix = jax.checkpoint(lambda p, h: _attention(p, h, D, mm))
        x = x + mix(p["mixer"], rms_norm(x, p["norm1"], eps))
        if mlp == "moe":
            h = rms_norm(x, p["norm2"], eps)
            if balance:
                scores = jax.nn.sigmoid(mm("bld,de->ble", h, p["mlp"]["router"]))
                bias = balanced_bias(scores.reshape(-1, D["E"]), D["k"])
                p["mlp"]["router_bias"] = bias
                stack[f"pos{i}"] = dict(
                    stack[f"pos{i}"], mlp=dict(stack[f"pos{i}"]["mlp"], router_bias=bias[None])
                )
            moe = jax.checkpoint(lambda p, h: _moe(p, h, D, conf, mm))
            x = x + moe(p["mlp"], h)
    return x, stack


def loss(params, tokens, labels, conf: Dict, mm):
    """Mean next-token cross entropy over the vocabulary slice, float32 throughout."""
    x, _ = _forward(params, tokens, conf, mm)
    x = rms_norm(x, params["final_norm"], conf["norm_eps"])
    return mean_cross_entropy(x, params["head"], labels, mm)


# ---------------------------------------------------------------------------
# Operation counts (no recompute)
# ---------------------------------------------------------------------------
def train_flops_per_token(conf: Dict, seq_len: int) -> float:
    """Forward and backward (3x forward) model operations per token: the
    projections, the conv, the SSD scan, causal attention, the router, the
    shared expert, the head, and the held experts at the balanced expectation
    of top_k x held / n_experts rows a token.  Gathers count nothing."""
    D = dims(conf)
    d, di, G, N, H, K = (D[k] for k in "d di G N H K".split())
    mamba = 2 * (d * (2 * di + 2 * G * N + H) + di * d) + 2 * K * (di + 2 * G * N)
    mamba += ssd_flops_per_token(D)
    q, kv = D["QH"] * D["hd"], D["KVH"] * D["hd"]
    attention = 2 * (d * (q + 2 * kv) + q * d) + 2 * 2 * q * (seq_len + 1) / 2
    rows = D["k"] * D["held"] / D["E"]
    moe = 2 * d * D["E"] + rows * 2 * 2 * d * D["f"] + 2 * 2 * d * D["fs"]
    forward = (
        D["ssm_layers"] * mamba + D["attn_layers"] * attention + D["moe_layers"] * moe
        + 2 * d * D["V"]
    )
    return 3.0 * forward


def ssd_kernel_cost(conf: Dict, batch: int, seq_len: int):
    """(operations, bytes) of one call of the SSD forward kernel: the scan's
    matmul work, each input read and each output written once (x, B, C and
    y in the configured dtype, dt and the final state in f32)."""
    D = dims(conf)
    H, P, G, N = D["H"], D["P"], D["G"], D["N"]
    act = jnp.dtype(conf["train"]["param_dtype"]).itemsize
    tokens = batch * seq_len
    flops = tokens * ssd_flops_per_token(D)
    nbytes = tokens * (2 * H * P * act + H * 4 + 2 * G * N * act) + batch * H * P * N * 4
    return float(flops), float(nbytes)


def gmm_kernel_cost(conf: Dict, rows: float, experts: float):
    """(operations, bytes) of one ``gmm`` or ``tgmm`` call of one MoE layer
    over ``rows`` real routed rows and the weights (or weight gradients) of
    ``experts`` experts: the (rows x d) by (d x f) product or its transpose;
    the two row operands read or written once and each expert's weight
    once, all in the configured dtype.  Padding rows count nothing.  (A
    ``gmm`` call fetches only the weights of experts that have rows; a
    ``tgmm`` call writes every held expert's gradient.)"""
    D = dims(conf)
    d, f = D["d"], D["f"]
    act = jnp.dtype(conf["train"]["param_dtype"]).itemsize
    flops = 2.0 * rows * d * f
    nbytes = (rows * (d + f) + experts * d * f) * act
    return float(flops), float(nbytes)
