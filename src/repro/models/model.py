"""Full model assembly: embeddings -> scanned period stack -> head / losses /
decode state.

A model is ``n_periods`` repetitions of the config's layer *period* (see
``ArchConfig``).  Parameters for period position ``i`` live under
``params["stack"][f"pos{i}"]`` with a leading ``n_periods`` axis, and the
stack is driven by ``jax.lax.scan`` so compile time and HLO size are
O(len(period)), not O(n_layers) — essential for lowering 72-layer models on
a 512-device mesh in this container.

Entry points (all pure functions over plain dict pytrees):

  init_params / param_shapes     parameters (real / ShapeDtypeStruct)
  forward                        token/frame embeddings -> final hidden
  forward_and_counters           ... and the MoE layers' counters
  train_loss                     chunked-vocab cross entropy (never
                                 materializes (B,S,V) for the full sequence)
  train_loss_and_counters        ... and the MoE layers' counters
  prefill                        forward + KV/SSM decode state
  init_decode_state / decode_step one-token serving step
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import (
    attention_block,
    attn_param_shapes,
    dense_init,
    mlp_block,
    mlp_param_shapes,
    rms_norm,
)
from repro.models.moe import MeshContext, moe_layer, moe_param_shapes
from repro.models.ssm import (
    ssm_block,
    ssm_block_decode,
    ssm_empty_carry,
    ssm_param_shapes,
)

# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------
_F32_LEAVES = ("A_log", "D", "dt_bias", "router_bias")  # small SSM / router params stay f32


def _mixer_shapes(cfg: ArchConfig, kind: str) -> dict:
    return attn_param_shapes(cfg) if kind == "attn" else ssm_param_shapes(cfg)


def _mlp_shapes(cfg: ArchConfig, kind: str) -> dict:
    if kind == "mlp":
        return mlp_param_shapes(cfg)
    if kind == "moe":
        return moe_param_shapes(cfg)
    return {}


def _position_shapes(cfg: ArchConfig, i: int) -> dict:
    mixer, mlp = cfg.period[i], cfg.mlp_pattern[i]
    shapes = {"norm1": (cfg.d_model,), "mixer": _mixer_shapes(cfg, mixer)}
    if mlp != "none":
        shapes["norm2"] = (cfg.d_model,)
        shapes["mlp"] = _mlp_shapes(cfg, mlp)
    return shapes


def _init_leaf(key, name: str, shape, cfg: ArchConfig, stacked: int = 0):
    """One parameter leaf.  ``stacked`` > 0 prepends the period axis."""
    full = (stacked, *shape) if stacked else tuple(shape)
    dt = jnp.float32 if name in _F32_LEAVES else cfg.jnp_dtype
    if name.startswith("norm") or name in ("gate_norm", "final_norm"):
        return jnp.ones(full, dt)
    if name in ("conv_b", "dt_bias", "router_bias") or name.startswith("b"):
        return jnp.zeros(full, dt)
    if name == "A_log":
        return jnp.zeros(full, dt)  # A = -exp(0) = -1
    if name == "D":
        return jnp.ones(full, dt)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return dense_init(key, full, dt, fan_in)


def _init_tree(key, tree, cfg: ArchConfig, stacked: int = 0):
    out = {}
    for name, sub in tree.items():
        key, sub_key = jax.random.split(key)
        if isinstance(sub, dict):
            out[name] = _init_tree(sub_key, sub, cfg, stacked)
        else:
            out[name] = _init_leaf(sub_key, name, sub, cfg, stacked)
    return out


def init_params(key: jax.Array, cfg: ArchConfig) -> Dict:
    """Real parameter pytree (use only for reduced/smoke configs!)."""
    keys = jax.random.split(key, len(cfg.period) + 3)
    params: Dict = {}
    if cfg.frontend != "frame":  # audio encoders take embeddings directly
        params["embed"] = dense_init(keys[0], (cfg.vocab, cfg.d_model), cfg.jnp_dtype, cfg.d_model)
    if not cfg.tie_embeddings:
        params["head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab), cfg.jnp_dtype, cfg.d_model)
    params["final_norm"] = jnp.ones((cfg.d_model,), cfg.jnp_dtype)
    params["stack"] = {
        f"pos{i}": _init_tree(keys[3 + i], _position_shapes(cfg, i), cfg, cfg.n_periods)
        for i in range(len(cfg.period))
    }
    return params


def param_shapes(cfg: ArchConfig) -> Dict:
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Embedding frontends
# ---------------------------------------------------------------------------
def embed_inputs(params: Dict, cfg: ArchConfig, batch: Dict) -> jax.Array:
    """(B, S, d) initial hidden states from the modality frontend.

    * text:   token embedding lookup
    * vlm:    token embedding; the first ``n_frontend_tokens`` positions are
              overwritten with precomputed patch embeddings (frontend stub)
    * audio:  precomputed frame embeddings *are* the input (no vocab lookup)
    """
    if cfg.frontend == "frame":
        return batch["frame_embeds"].astype(cfg.jnp_dtype)
    x = params["embed"][batch["tokens"]]  # (B, S, d)
    if cfg.frontend == "patch":
        patches = batch["patch_embeds"].astype(x.dtype)  # (B, P, d)
        x = jax.lax.dynamic_update_slice(x, patches, (0, 0, 0))
    return x


# ---------------------------------------------------------------------------
# The period body (one repetition of cfg.period)
# ---------------------------------------------------------------------------
def _channel_mix(p: dict, x, cfg: ArchConfig, kind: str, ctx: Optional[MeshContext]):
    """(x + channel mixer, counters): the MoE layer's counters, else none."""
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    if kind == "moe":
        y, counters = moe_layer(p["mlp"], h, cfg, ctx)
        return x + y, counters
    return x + mlp_block(p["mlp"], h, cfg), {}


def _period_forward(
    pslice: dict,
    x: jax.Array,
    cfg: ArchConfig,
    ctx: Optional[MeshContext],
    positions: jax.Array,
    collect_cache: bool,
    inner_remat: bool = False,
):
    """One period over a full sequence (train / prefill).

    Returns (x, caches, counters) where caches[f"pos{i}"] holds the decode
    carry for position ``i`` (attn: dict(k,v); ssm: dict(state,conv)) when
    ``collect_cache`` — else an empty dict — and counters sums the
    period's MoE counters (``moe_layer``).

    ``inner_remat`` additionally checkpoints every SUBLAYER, so the backward
    pass holds one sublayer's FSDP-gathered weights + intermediates at a
    time instead of the whole period's — this is what keeps the long-period
    MoE hybrids (jamba: 8 sublayers with 4 expert banks per period) inside
    the 16 GB/chip HBM budget.
    """

    def ck(f, *args):
        return jax.checkpoint(f)(*args) if inner_remat else f(*args)

    caches, counters = {}, {}
    for i, (mixer, mlp) in enumerate(zip(cfg.period, cfg.mlp_pattern)):
        p = pslice[f"pos{i}"]
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if mixer == "attn":
            y, _ = ck(
                lambda pm, hh: attention_block(pm, hh, cfg, positions=positions),
                p["mixer"], h,
            )
            if collect_cache:
                # Recompute K/V cheaply for the cache (avoids threading them
                # out of attention_block's chunked path).
                from repro.models.layers import apply_rope  # local import

                B, S, _ = h.shape
                k = jnp.einsum("bsd,dq->bsq", h, p["mixer"]["wk"]).reshape(
                    B, S, cfg.n_kv_heads, cfg.head_dim
                )
                v = jnp.einsum("bsd,dq->bsq", h, p["mixer"]["wv"]).reshape(
                    B, S, cfg.n_kv_heads, cfg.head_dim
                )
                if cfg.qkv_bias:
                    k = k + p["mixer"]["bk"].reshape(1, 1, cfg.n_kv_heads, cfg.head_dim)
                    v = v + p["mixer"]["bv"].reshape(1, 1, cfg.n_kv_heads, cfg.head_dim)
                if cfg.pos_emb == "rope":
                    k = apply_rope(k, positions, cfg.rope_theta)
                caches[f"pos{i}"] = {"k": k.astype(cfg.jnp_dtype), "v": v.astype(cfg.jnp_dtype)}
        else:
            y, carry = ck(lambda pm, hh: ssm_block(pm, hh, cfg), p["mixer"], h)
            if collect_cache:
                caches[f"pos{i}"] = {"state": carry[0], "conv": carry[1]}
        x = x + y
        if mlp != "none":
            x, more = ck(
                lambda pp, xx, kind=mlp: _channel_mix(pp, xx, cfg, kind, ctx), p, x
            )
            counters = {k: counters.get(k, 0) + v for k, v in more.items()} or counters
    return x, caches, counters


def _period_decode(
    pslice: dict,
    cslice: dict,
    x: jax.Array,
    cfg: ArchConfig,
    ctx: Optional[MeshContext],
    cache_pos: jax.Array,
    kv_len: jax.Array,
):
    """One period for one new token. cslice holds this period's caches."""
    new_caches = {}
    positions = jnp.reshape(cache_pos, (1,))
    for i, (mixer, mlp) in enumerate(zip(cfg.period, cfg.mlp_pattern)):
        p = pslice[f"pos{i}"]
        c = cslice[f"pos{i}"]
        h = rms_norm(x, p["norm1"], cfg.norm_eps)
        if mixer == "attn":
            y, kv = attention_block(
                p["mixer"],
                h,
                cfg,
                positions=positions,
                kv_cache=(c["k"], c["v"]),
                cache_pos=cache_pos,
                kv_len=kv_len,
            )
            new_caches[f"pos{i}"] = {"k": kv[0], "v": kv[1]}
        else:
            y, carry = ssm_block_decode(p["mixer"], h, cfg, (c["state"], c["conv"]))
            new_caches[f"pos{i}"] = {"state": carry[0], "conv": carry[1]}
        x = x + y
        if mlp != "none":
            x, _ = _channel_mix(p, x, cfg, mlp, ctx)
    return x, new_caches


# ---------------------------------------------------------------------------
# Full forward passes
# ---------------------------------------------------------------------------
def forward(params: Dict, cfg: ArchConfig, batch: Dict, ctx: Optional[MeshContext] = None,
            **kwargs):
    """Embeddings -> scanned stack -> final norm: ``forward_and_counters``
    without the counters."""
    hidden, caches, _ = forward_and_counters(params, cfg, batch, ctx, **kwargs)
    return hidden, caches


def forward_and_counters(
    params: Dict,
    cfg: ArchConfig,
    batch: Dict,
    ctx: Optional[MeshContext] = None,
    *,
    remat: bool = True,
    collect_cache: bool = False,
    act_spec=None,
    remat_policy: Optional[str] = "minimal",
):
    """Embeddings -> scanned stack -> final norm.

    Returns (hidden (B,S,d), caches, counters) — caches stacked over
    periods when ``collect_cache`` (prefill), else None; counters the MoE
    layers' counters summed over the stack (empty without MoE).

    ``act_spec`` (a PartitionSpec for (B, S, d)) pins the activation
    sharding at every period boundary — without it GSPMD is free to
    replicate the scan carry across the batch axes, which multiplies
    activation memory by the data-parallel degree.

    ``remat_policy``: "minimal" saves only the period carries (full
    recompute in backward — the memory floor); "dots" additionally saves
    projection outputs (checkpoint_policies.dots_with_no_batch_dims);
    "sublayer" nests a checkpoint around every sublayer so backward peaks
    at ONE sublayer's gathered weights/intermediates (long-period MoE
    hybrids).
    """
    x = embed_inputs(params, cfg, batch)
    S = x.shape[1]
    positions = jnp.arange(S)

    def constrain(t):
        if act_spec is not None:
            return jax.lax.with_sharding_constraint(t, act_spec)
        return t

    x = constrain(x)
    inner = remat and remat_policy == "sublayer"

    def body(carry, pslice):
        y, caches, counters = _period_forward(
            pslice, carry, cfg, ctx, positions, collect_cache, inner_remat=inner
        )
        return constrain(y), (caches if collect_cache else None, counters)

    if remat:
        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if remat_policy == "dots"
            else None
        )
        body = jax.checkpoint(body, policy=policy)
    x, (caches, counters) = jax.lax.scan(body, x, params["stack"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    counters = {k: v.sum() for k, v in counters.items()}
    return x, (caches if collect_cache else None), counters


def lm_head(params: Dict, cfg: ArchConfig, hidden: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return jnp.einsum("bsd,dv->bsv", hidden, w, preferred_element_type=jnp.float32)


def chunked_ce_loss(
    params: Dict,
    cfg: ArchConfig,
    hidden: jax.Array,
    labels: jax.Array,
    *,
    chunk: int = 512,
) -> jax.Array:
    """Cross entropy, scanned over sequence chunks so the (B, S, V) logits
    tensor never exists for more than ``chunk`` positions at a time.  With
    the head sharded over ``model`` on V, the logsumexp / one-hot reductions
    lower to partial reductions + a small all-reduce — no vocab gather.

    labels < 0 are masked out (padding / modality-frontend positions).
    """
    B, S, d = hidden.shape
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    chunk = min(chunk, S)
    n = S // chunk
    hs = hidden[:, : n * chunk].reshape(B, n, chunk, d)
    ls = labels[:, : n * chunk].reshape(B, n, chunk)

    def body(carry, xs):
        tot, cnt = carry
        h_c, l_c = xs  # (B, c, d), (B, c)
        logits = jnp.einsum(
            "bcd,dv->bcv", h_c, w, preferred_element_type=jnp.float32
        )  # f32 (B, c, V)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)  # (B, c)
        onehot = jax.nn.one_hot(jnp.maximum(l_c, 0), cfg.vocab, dtype=logits.dtype)
        gold = (logits * onehot).sum(-1)
        mask = (l_c >= 0).astype(jnp.float32)
        tot = tot + ((lse - gold) * mask).sum()
        cnt = cnt + mask.sum()
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(body),  # recompute chunk logits in bwd: peak = ONE chunk
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (jnp.moveaxis(hs, 1, 0), jnp.moveaxis(ls, 1, 0)),
    )
    return tot / jnp.maximum(cnt, 1.0)


def train_loss(
    params: Dict,
    cfg: ArchConfig,
    batch: Dict,
    ctx: Optional[MeshContext] = None,
    act_spec=None,
    remat_policy: Optional[str] = "minimal",
) -> jax.Array:
    return train_loss_and_counters(params, cfg, batch, ctx, act_spec, remat_policy)[0]


def train_loss_and_counters(
    params: Dict,
    cfg: ArchConfig,
    batch: Dict,
    ctx: Optional[MeshContext] = None,
    act_spec=None,
    remat_policy: Optional[str] = "minimal",
):
    """(loss, counters): the training loss and the stack's MoE counters."""
    hidden, _, counters = forward_and_counters(
        params, cfg, batch, ctx, remat=True, act_spec=act_spec, remat_policy=remat_policy
    )
    return chunked_ce_loss(params, cfg, hidden, batch["labels"]), counters


# ---------------------------------------------------------------------------
# Serving: prefill + one-token decode
# ---------------------------------------------------------------------------
def prefill(
    params: Dict,
    cfg: ArchConfig,
    batch: Dict,
    ctx: Optional[MeshContext] = None,
    act_spec=None,
):
    """Process the prompt; returns (last-position logits f32 (B, V), state).

    state = (caches stacked over periods, kv_len (B,) int32).
    """
    hidden, caches = forward(
        params, cfg, batch, ctx, remat=False, collect_cache=True, act_spec=act_spec
    )
    logits = lm_head(params, cfg, hidden[:, -1:])[:, 0]
    B, S = hidden.shape[0], hidden.shape[1]
    kv_len = jnp.full((B,), S, jnp.int32)
    return logits, (caches, kv_len)


def init_decode_state(cfg: ArchConfig, batch: int, max_len: int) -> Tuple[Dict, jax.Array]:
    """Empty decode state sized for a ``max_len`` context (cells: decode_32k,
    long_500k build this with max_len = seq_len)."""
    caches = {}
    for i, mixer in enumerate(cfg.period):
        if mixer == "attn":
            kv = jnp.zeros((cfg.n_periods, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cfg.jnp_dtype)
            caches[f"pos{i}"] = {"k": kv, "v": kv}
        else:
            st, conv = ssm_empty_carry(cfg, batch)
            caches[f"pos{i}"] = {
                "state": jnp.zeros((cfg.n_periods, *st.shape), st.dtype),
                "conv": jnp.zeros((cfg.n_periods, *conv.shape), conv.dtype),
            }
    kv_len = jnp.zeros((batch,), jnp.int32)
    return caches, kv_len


def decode_step(
    params: Dict,
    cfg: ArchConfig,
    tokens: jax.Array,  # (B, 1) int32
    state: Tuple[Dict, jax.Array],
    cache_pos: jax.Array,  # scalar int32: slot the new token occupies
    ctx: Optional[MeshContext] = None,
    act_spec=None,
):
    """One serving step: consume one token, emit next-token logits.

    Returns (logits f32 (B, V), new_state).
    """
    caches, kv_len = state
    x = params["embed"][tokens]  # (B, 1, d)
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    new_kv_len = jnp.maximum(kv_len, cache_pos + 1)

    def body(carry, xs):
        pslice, cslice = xs
        y, new_c = _period_decode(pslice, cslice, carry, cfg, ctx, cache_pos, new_kv_len)
        return y, new_c

    x, new_caches = jax.lax.scan(body, x, (params["stack"], caches))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(params, cfg, x)[:, 0]
    return logits, (new_caches, new_kv_len)
