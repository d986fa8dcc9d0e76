"""Step-function builders: the jit-able train / prefill / decode steps the
launcher, dry-run, and examples all share.

Each builder closes over (cfg, mesh-context, opt settings) and returns a
function of *arrays only*, so ``jax.jit(step).lower(*specs)`` works with
ShapeDtypeStruct stand-ins.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ShardingRules
from repro.models import model as M
from repro.models.config import ArchConfig, ShapeConfig
from repro.models.moe import MeshContext
from repro.training.optimizer import OptSettings, adamw_update


def _fitting_batch_axes(rules: ShardingRules, global_batch: int):
    axes, b = [], global_batch
    for a in rules.fsdp_axes:
        n = rules.mesh.shape[a]
        if b % n == 0:
            axes.append(a)
            b //= n
    return tuple(axes)


def make_mesh_context(
    rules: Optional[ShardingRules], cfg: ArchConfig, global_batch: int
) -> Optional[MeshContext]:
    """MeshContext for the MoE shard_map path: only the fsdp axes that
    evenly divide the batch are used as batch axes (batch=1 long-context
    decode runs with a fully replicated token set inside the MoE)."""
    if rules is None or "moe" not in cfg.mlp_pattern:
        return None
    axes = _fitting_batch_axes(rules, global_batch)
    return MeshContext(rules.mesh, batch_axes=axes, model_axis=rules.model_axis)


def act_partition_spec(
    rules: Optional[ShardingRules], global_batch: int
) -> Optional[P]:
    """Activation spec (B, S, d): batch over the fitting fsdp axes."""
    if rules is None:
        return None
    axes = _fitting_batch_axes(rules, global_batch)
    return P(axes or None, None, None)


def auto_microbatches(
    cfg: ArchConfig, shape: ShapeConfig, rules: Optional[ShardingRules],
    act_budget_bytes: float = 3e9,
) -> int:
    """Gradient-accumulation factor so the per-device remat-saved activation
    carries (n_layers x microbatch_local x S x d x 2B) fit the budget."""
    if rules is None:
        return 1
    dp = 1
    b = shape.global_batch
    for a in rules.fsdp_axes:
        n = rules.mesh.shape[a]
        if b % n == 0:
            dp *= n
            b //= n
    local_b = shape.global_batch // dp
    per_layer = shape.seq_len * cfg.d_model * 2  # bf16 carry per sample
    n = 1
    while (
        n < local_b
        and local_b % (2 * n) == 0
        and cfg.n_layers * (local_b // n) * per_layer > act_budget_bytes
    ):
        n *= 2
    return n


def make_train_step(
    cfg: ArchConfig,
    settings: OptSettings,
    rules: Optional[ShardingRules] = None,
    global_batch: int = 0,
    remat_policy: str = "minimal",
    microbatches: int = 1,
):
    """fwd+bwd+AdamW: ``(loss, counters), params, opt_state``, where
    counters are the MoE layers' (``M.train_loss_and_counters``; empty
    without MoE).  ``microbatches`` > 1 accumulates gradients over a
    lax.scan of batch slices — per-step activation memory drops by the
    factor while arithmetic is unchanged (the standard way the 35-400B
    train_4k cells fit 16 GB/chip HBM)."""
    ctx = make_mesh_context(rules, cfg, global_batch)
    act = act_partition_spec(rules, global_batch)
    grad_fn = jax.value_and_grad(M.train_loss_and_counters, has_aux=True)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, counters), grads = grad_fn(params, cfg, batch, ctx, act, remat_policy)
        else:
            def slice_batch(i):
                def f(x):
                    mb = x.shape[0] // microbatches
                    return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
                return jax.tree.map(f, batch)

            def body(carry, i):
                loss_acc, grads_acc = carry
                (loss_i, counters_i), grads_i = grad_fn(
                    params, cfg, slice_batch(i), ctx, act, remat_policy
                )
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grads_acc, grads_i
                )
                return (loss_acc + loss_i, grads_acc), counters_i

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss, grads), counters = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), jnp.arange(microbatches)
            )
            counters = {k: v.sum() for k, v in counters.items()}
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        params, opt_state = adamw_update(params, grads, opt_state, settings)
        return (loss, counters), params, opt_state

    return train_step


def make_prefill_step(
    cfg: ArchConfig, rules: Optional[ShardingRules] = None, global_batch: int = 0
):
    ctx = make_mesh_context(rules, cfg, global_batch)
    act = act_partition_spec(rules, global_batch)

    def prefill_step(params, batch):
        return M.prefill(params, cfg, batch, ctx, act)

    return prefill_step


def make_decode_step(
    cfg: ArchConfig, rules: Optional[ShardingRules] = None, global_batch: int = 0
):
    ctx = make_mesh_context(rules, cfg, global_batch)
    act = act_partition_spec(rules, global_batch)

    def serve_step(params, state, tokens, cache_pos):
        return M.decode_step(params, cfg, tokens, state, cache_pos, ctx, act)

    return serve_step


def make_encoder_step(
    cfg: ArchConfig, rules: Optional[ShardingRules] = None, global_batch: int = 0
):
    """Encoder-only 'prefill': full forward + per-frame logits (no cache)."""
    ctx = make_mesh_context(rules, cfg, global_batch)
    act = act_partition_spec(rules, global_batch)

    def encode_step(params, batch):
        hidden, _ = M.forward(params, cfg, batch, ctx, remat=False, act_spec=act)
        return M.lm_head(params, cfg, hidden)

    return encode_step


def step_for_cell(
    cfg: ArchConfig,
    shape: ShapeConfig,
    rules: Optional[ShardingRules] = None,
    settings: Optional[OptSettings] = None,
    microbatches: Optional[int] = None,
    remat_policy: str = "minimal",
):
    """(step_fn, takes_params_and_opt, microbatches) for one cell."""
    B = shape.global_batch
    if shape.kind == "train":
        settings = settings or OptSettings.auto(cfg.param_count())
        if microbatches is None:
            microbatches = auto_microbatches(cfg, shape, rules)
        return (
            make_train_step(
                cfg, settings, rules, B,
                remat_policy=remat_policy, microbatches=microbatches,
            ),
            True,
            microbatches,
        )
    if shape.kind == "prefill":
        if cfg.is_encoder:
            return make_encoder_step(cfg, rules, B), False, 1
        return make_prefill_step(cfg, rules, B), False, 1
    return make_decode_step(cfg, rules, B), False, 1
