"""Mixture-of-Experts FFN with TPU-native expert parallelism.

Two layers.  ``moe_layer`` picks by ``capacity_factor``: above 0 the
capacity layer below; 0 the dropless layer (``routed_experts``), which
holds ``experts_held`` of the experts, routes over all of them, computes
every row routed to its own through grouped matmuls, and adds a shared
expert.

Capacity layer: routing is top-k with capacity-based token dropping (GShard-style,
first-come-first-served by sequence position), expressed WITHOUT the giant
(tokens, experts, capacity) one-hot dispatch tensors: each expert *selects*
its assigned tokens with ``lax.top_k`` over an assignment score, computes a
dense (capacity, d_ff) FFN, and scatter-adds the result back.  All shapes
are static => AOT-lowerable for the dry-run.

Distribution: experts are sharded over the ``model`` mesh axis (EP ≡ TP for
the FFN).  Under tensor parallelism the block input is already replicated
across ``model``, so dispatch needs NO all-to-all at all: every shard
locally selects the tokens routed to *its* experts, computes them, and a
single ``psum`` over ``model`` combines expert outputs — the same collective
a TP dense FFN would need anyway.  (This adaptation — replicated-activation
EP instead of GPU-style all-to-all EP — is recorded in DESIGN.md §7.)

Both the sharded path (shard_map) and the local path (no mesh) share
``_expert_select_compute``, so tests can assert they agree exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.gmm import group_starts as gmm_group_starts
from repro.kernels.ops import grouped_matmul
from repro.models.config import ArchConfig
from repro.models.layers import activation


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """How model code sees the device mesh (None => single-process local)."""

    mesh: object  # jax.sharding.Mesh
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]


def router_probs(x_flat: jax.Array, router_w: jax.Array, top_k: int):
    """Top-k routing with renormalized combine weights.

    Returns (topk_idx (T,k) int32, topk_w (T,k) f32).
    """
    logits = jnp.einsum(
        "td,de->te", x_flat, router_w, preferred_element_type=jnp.float32
    )
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, top_k)
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    return topk_idx, topk_w


def _expert_weight(topk_idx, topk_w, e: jax.Array, n_experts: int):
    """Combine weight of expert ``e`` for every token (0 if not routed)."""
    sel = (topk_idx == e).astype(topk_w.dtype)  # (T, k)
    return (topk_w * sel).sum(-1)  # (T,)


def _expert_select_compute(
    x_flat: jax.Array,  # (T, d)
    weight: jax.Array,  # (T,) combine weight for this expert (0 = unrouted)
    w_gate: jax.Array,  # (d, f)
    w_up: jax.Array,
    w_down: jax.Array,  # (f, d)
    capacity: int,
    act: str,
) -> jax.Array:
    """One expert: select (<= capacity) assigned tokens, FFN, scatter back."""
    T, d = x_flat.shape
    assigned = weight > 0
    # Earlier tokens win capacity (GShard FCFS). Score: T-pos for assigned,
    # -1 for unassigned — top_k picks assigned tokens in position order.
    score = jnp.where(assigned, T - jnp.arange(T, dtype=jnp.int32), -1)
    top_scores, idx = jax.lax.top_k(score, min(capacity, T))
    valid = (top_scores > 0).astype(jnp.float32)  # (C,)
    xsel = x_flat[idx]  # (C, d)
    g = jnp.einsum("cd,df->cf", xsel, w_gate, preferred_element_type=jnp.float32)
    if act == "swiglu":
        u = jnp.einsum("cd,df->cf", xsel, w_up, preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(g)
    y = jnp.einsum(
        "cf,fd->cd", h.astype(x_flat.dtype), w_down, preferred_element_type=jnp.float32
    )
    scale = (weight[idx] * valid)[:, None]  # zero out invalid slots
    out = jnp.zeros((T, d), jnp.float32).at[idx].add(y * scale)
    return out


def _capacity(n_tokens: int, cfg: ArchConfig, capacity_factor: Optional[float]) -> int:
    f = capacity_factor if capacity_factor is not None else cfg.capacity_factor
    return max(1, int(n_tokens * cfg.top_k / cfg.n_experts * f))


def _moe_local(params, x_flat, cfg: ArchConfig, capacity: int, n_local: int, e0):
    """Compute ``n_local`` experts starting at id ``e0`` over local tokens."""
    topk_idx, topk_w = router_probs(x_flat, params["router"], cfg.top_k)

    def one_expert(i, acc):
        e = e0 + i
        w = _expert_weight(topk_idx, topk_w, e, cfg.n_experts)
        out = _expert_select_compute(
            x_flat,
            w,
            params["w_gate"][i],
            params["w_up"][i] if cfg.mlp_act == "swiglu" else params["w_gate"][i],
            params["w_down"][i],
            capacity,
            cfg.mlp_act,
        )
        return acc + out

    acc0 = jnp.zeros(x_flat.shape, jnp.float32)
    return jax.lax.fori_loop(0, n_local, one_expert, acc0)


# ---------------------------------------------------------------------------
# Dropless: every routed row on a held expert is computed
# ---------------------------------------------------------------------------
#: Rows of a grouped-matmul tile: each held expert's rows start on one.
GMM_TILE = 128


def route(x_flat: jax.Array, params: dict, cfg: ArchConfig):
    """Top-k over all ``cfg.n_experts`` -> (topk_idx (T,k) int32, weights (T,k) f32).

    The ``sigmoid_bias`` router (DeepSeek-V3 and Nemotron-H): sigmoid scores
    in f32; ``router_bias`` is added for the choice only; the chosen scores
    are normalised to sum 1 and scaled by ``routed_scale``.
    """
    logits = jnp.einsum("td,de->te", x_flat, params["router"], preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + params["router_bias"].astype(jnp.float32), cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scale


def _dropless_rows(idx, w, e0, held: int, tm: int):
    """Lay the (token, slot) assignments that land on experts [e0, e0+held)
    out by expert, each expert's rows from a tile boundary (``gmm``'s
    layout).  Returns (token (M,), weight (M,), sizes (held,)): the token
    each row computes (T for a row that holds none) and its combine weight
    (0 there)."""
    T, k = idx.shape
    local = idx.reshape(-1) - e0  # (T*k,)
    mine = (local[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)  # (T*k, held)
    sizes = mine.sum(0)
    rank = jnp.sum((jnp.cumsum(mine, axis=0) - 1) * mine, axis=1)  # place within its expert
    held_here = (local >= 0) & (local < held)
    n_rows = -(-(T * min(k, held) + held * (tm - 1)) // tm) * tm  # the most any routing needs
    dest = jnp.where(
        held_here, gmm_group_starts(sizes, tm)[jnp.clip(local, 0, held - 1)] + rank, n_rows
    )
    token = jnp.full((n_rows,), T, jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop"
    )
    weight = jnp.zeros((n_rows,), jnp.float32).at[dest].set(w.reshape(-1), mode="drop")
    return token, weight, sizes


def routed_experts(params: dict, x_flat: jax.Array, cfg: ArchConfig, e0=0):
    """The held experts' part of the layer, dropless: route over every
    expert, sort the rows that land on experts [e0, e0 + held) by expert,
    run each expert's relu2 MLP as two grouped matmuls (kernels/gmm.py),
    and scatter-add the rows back weighted.  Returns ((T, d) f32, counters):
    ``moe_rows`` the rows computed, ``moe_rows_max`` the largest expert's,
    ``moe_experts`` the held experts with any (whose weights are read)."""
    T, d = x_flat.shape
    held = cfg.n_experts_held
    with jax.named_scope("moe.route"):
        idx, w = route(x_flat, params, cfg)
    with jax.named_scope("moe.dispatch"):
        token, weight, sizes = _dropless_rows(idx, w, e0, held, GMM_TILE)
        rows = jnp.take(x_flat, token, axis=0, mode="fill", fill_value=0)
    with jax.named_scope("moe.gmm"):
        h = grouped_matmul(rows, params["w_up"], sizes, tm=GMM_TILE)
        h = activation(h.astype(jnp.float32), cfg.mlp_act).astype(x_flat.dtype)
        y = grouped_matmul(h, params["w_down"], sizes, tm=GMM_TILE)
    with jax.named_scope("moe.combine"):
        out = jnp.zeros((T, d), jnp.float32).at[token].add(
            y.astype(jnp.float32) * weight[:, None], mode="drop"
        )
    counters = {"moe_rows": sizes.sum(), "moe_rows_max": sizes.max(),
                "moe_experts": jnp.sum(sizes > 0)}
    return out, counters


def shared_expert(params: dict, x_flat: jax.Array, cfg: ArchConfig) -> jax.Array:
    """The shared expert every token passes, (T, d) f32."""
    with jax.named_scope("moe.shared"):
        h = jnp.einsum("td,df->tf", x_flat, params["shared_up"], preferred_element_type=jnp.float32)
        h = activation(h, cfg.mlp_act).astype(x_flat.dtype)
        return jnp.einsum("tf,fd->td", h, params["shared_down"], preferred_element_type=jnp.float32)


def moe_layer(params: dict, x: jax.Array, cfg: ArchConfig, ctx: Optional[MeshContext] = None):
    """The MoE FFN and its counters: ``(y (B,S,d), counters)``.  Counters are
    empty for the capacity layer."""
    if cfg.capacity_factor > 0:
        return moe_block(params, x, cfg, ctx), {}
    if ctx is not None and ctx.model_size > 1:
        raise NotImplementedError("the dropless layer runs on one device's expert share")
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    y, counters = routed_experts(params, x_flat, cfg)
    if cfg.shared_expert_ff:
        y = y + shared_expert(params, x_flat, cfg)
    return y.astype(x.dtype).reshape(B, S, d), counters


def moe_block(
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    ctx: Optional[MeshContext] = None,
    capacity_factor: Optional[float] = None,
) -> jax.Array:
    """MoE FFN with capacity. params: router (d,E), w_gate/w_up (E,d,f), w_down (E,f,d)."""
    B, S, d = x.shape
    dt = x.dtype

    if ctx is None or ctx.model_size == 1:
        x_flat = x.reshape(B * S, d)
        cap = _capacity(B * S, cfg, capacity_factor)
        y = _moe_local(params, x_flat, cfg, cap, cfg.n_experts, 0)
        return y.astype(dt).reshape(B, S, d)

    if cfg.n_experts % ctx.model_size != 0:
        raise ValueError(
            f"n_experts={cfg.n_experts} not divisible by model axis {ctx.model_size}"
        )
    n_local = cfg.n_experts // ctx.model_size
    bd = P(ctx.batch_axes, None, None)
    ma = ctx.model_axis

    def sharded(x_loc, router_w, w_gate, w_up, w_down):
        # x_loc: (B_loc, S, d) — replicated over `model`; experts sharded.
        Bl, Sl, dl = x_loc.shape
        x_flat = x_loc.reshape(Bl * Sl, dl)
        cap = _capacity(Bl * Sl, cfg, capacity_factor)
        e0 = jax.lax.axis_index(ma) * n_local
        p_loc = {"router": router_w, "w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        y = _moe_local(p_loc, x_flat, cfg, cap, n_local, e0)
        # Combine expert outputs in the model dtype: each shard's partial sum
        # is already an f32 accumulation; the cross-shard psum carries bf16
        # (halves the per-layer combine collective — grad-compression-style).
        y = jax.lax.psum(y.astype(dt), ma)
        return y.reshape(Bl, Sl, dl)

    w_up = params["w_up"] if cfg.mlp_act == "swiglu" else params["w_gate"]
    y = jax.shard_map(
        sharded,
        mesh=ctx.mesh,
        in_specs=(bd, P(None, None), P(ma, None, None), P(ma, None, None), P(ma, None, None)),
        out_specs=bd,
        check_vma=False,
    )(x, params["router"], params["w_gate"], w_up, params["w_down"])
    return y.astype(dt)


def moe_param_shapes(cfg: ArchConfig) -> dict:
    d, f, E, held = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_experts_held
    if cfg.mlp_act == "relu2":
        shapes = {"router": (d, E), "w_up": (held, d, f), "w_down": (held, f, d)}
    else:
        shapes = {"router": (d, E), "w_gate": (held, d, f), "w_down": (held, f, d)}
    if cfg.mlp_act == "swiglu":
        shapes["w_up"] = (held, d, f)
    if cfg.router == "sigmoid_bias":
        shapes["router_bias"] = (E,)
    if cfg.shared_expert_ff:
        shapes["shared_up"] = (d, cfg.shared_expert_ff)
        shapes["shared_down"] = (cfg.shared_expert_ff, d)
    return shapes


def moe_reference(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Oracle: dense all-experts compute, exact top-k combine, NO capacity
    limit.  moe_block converges to this as capacity_factor -> inf."""
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d).astype(jnp.float32)
    topk_idx, topk_w = router_probs(x_flat, params["router"], cfg.top_k)
    y = jnp.zeros_like(x_flat)
    for e in range(cfg.n_experts):
        g = x_flat @ params["w_gate"][e].astype(jnp.float32)
        if cfg.mlp_act == "swiglu":
            u = x_flat @ params["w_up"][e].astype(jnp.float32)
            h = jax.nn.silu(g) * u
        else:
            h = jax.nn.gelu(g)
        out = h @ params["w_down"][e].astype(jnp.float32)
        w = (topk_w * (topk_idx == e)).sum(-1)
        y = y + out * w[:, None]
    return y.astype(x.dtype).reshape(B, S, d)
