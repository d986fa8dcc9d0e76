"""The plain reference for the training cells, and the comparison with it.

It imports nothing of the program.  A model file under ``bench/models``
gives the forward pass and the loss; this module trains it for the first
steps with AdamW and compares what the program did on those same steps.

Precision.  ``"fp32"`` computes every matmul in float32 at ``HIGHEST``
precision (on a TPU a float32 matmul otherwise runs in bf16 passes).
``"fp8"`` is the control: the same model with each weight matmul fed
per-tensor scaled float8 operands (e4m3 forward, e5m2 for the gradient on
its way back), the path that would tempt a later change below the bf16
that the configurations state.  Both store parameters in the configured
dtype after every update and keep the moments in float32, as stated.

Numbers compared (each a share, 0 = identical):

* ``loss_gap``: the largest relative gap of the first steps' losses;
* ``grad_gap``: over the leaves, the largest gap between the norms of the
  first gradient as the optimizer took it (after global-norm clipping),
  over the reference leaf's norm or the median leaf's, whichever is larger;
* ``grad_diff``: the same with the norm of the two first gradients'
  difference in place of the gap of their norms.  A gap of norms is blind
  to rounding that scatters evenly (it moves a norm at second order), so
  it hardly tells bf16 from float8; the difference does, at first order;
* ``update_gap``: the same for the parameters' change over the first
  steps, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's (they move by round-off alone).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: Leaves whose reference gradient norm is under this share of the median
#: leaf's are left out of ``update_gap``.
STILL_LEAF = 1e-3
#: Tokens per block of the chunked loss (the logits of one block at a time).
LOSS_BLOCK = 256


# ---------------------------------------------------------------------------
# Matmuls in the reference's and the control's precision
# ---------------------------------------------------------------------------
def _round_trip(x, dtype, top: float):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _round_trip(x, jnp.float8_e4m3fn, 448.0)


_fp8_operand.defvjp(lambda x: (_fp8_operand(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


_fp8_cotangent.defvjp(
    lambda y: (y, None), lambda _, g: (_round_trip(g, jnp.float8_e5m2, 57344.0),)
)


def matmul(precision: str) -> Callable:
    """``mm(subscripts, x, w)``: an einsum of activations by weights."""
    if precision == "fp32":
        return lambda s, x, w: jnp.einsum(s, x, w, precision=HIGHEST)
    if precision == "fp8":
        return lambda s, x, w: _fp8_cotangent(
            jnp.einsum(s, _fp8_operand(x), _fp8_operand(w), precision=HIGHEST)
        )
    raise ValueError(f"unknown precision {precision!r}")


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def mean_cross_entropy(hidden, w, labels, mm):
    """Mean token cross entropy of ``hidden @ w`` against ``labels``, one
    block of positions at a time so the logits of the whole batch never
    exist at once."""
    B, S, d = hidden.shape
    blk = min(LOSS_BLOCK, S)
    n = S // blk
    hs = jnp.moveaxis(hidden.reshape(B, n, blk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, n, blk), 1, 0)

    def block(total, xs):
        h, lab = xs
        logits = mm("bsd,dv->bsv", h, w)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(jax.checkpoint(block), jnp.zeros((), jnp.float32), (hs, ls))
    return total / (B * S)


# ---------------------------------------------------------------------------
# Norms by leaf
# ---------------------------------------------------------------------------
def leaf_norms(tree) -> List[jax.Array]:
    """Float32 Frobenius norm of every leaf, in flattening order."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)]


# ---------------------------------------------------------------------------
# Training the reference
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Readings:
    """What one side (program, reference or control) did on the first steps."""

    losses: List[float]
    grad_norms: List[float]  # first gradient after clipping, by leaf
    delta_norms: List[float]  # parameters' change over the steps, by leaf
    raw_grad_norms: List[float] = dataclasses.field(default_factory=list)
    first_grad: object = None  # the first gradient after clipping (a tree)


@dataclasses.dataclass(frozen=True)
class Adam:
    lr: float
    beta1: float
    beta2: float
    eps: float
    weight_decay: float
    grad_clip: float

    @classmethod
    def from_train(cls, train: Dict) -> "Adam":
        return cls(**{f.name: float(train[f.name]) for f in dataclasses.fields(cls)})


def train_reference(
    loss_fn: Callable,
    make_params: Callable,
    batches: Sequence[Dict[str, np.ndarray]],
    adam: Adam,
    precision: str,
) -> Readings:
    """AdamW (decoupled decay on matrices, global-norm clipping) over
    ``batches``, one jitted step at a time, from ``make_params()`` as
    stored.  State is donated step to step, and the starting parameters are
    made again for the change, so that the reference holds one copy of its
    state."""
    mm = matmul(precision)
    grad = jax.value_and_grad(lambda p, t, l: loss_fn(p, t, l, mm))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, tokens, labels):
        f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        loss, g = grad(f32, tokens, labels)
        raw = leaf_norms(g)
        total = jnp.sqrt(sum(jnp.square(n) for n in raw))
        clip = jnp.minimum(1.0, adam.grad_clip / jnp.maximum(total, 1e-9))
        g = jax.tree.map(lambda x: x * clip, g)
        m = jax.tree.map(lambda a, b: adam.beta1 * a + (1 - adam.beta1) * b, m, g)
        v = jax.tree.map(lambda a, b: adam.beta2 * a + (1 - adam.beta2) * b * b, v, g)
        c1 = 1 - adam.beta1 ** t
        c2 = 1 - adam.beta2 ** t

        def update(p, p32, a, b):
            u = (a / c1) / (jnp.sqrt(b / c2) + adam.eps)
            if p.ndim > 1:
                u = u + adam.weight_decay * p32
            return (p32 - adam.lr * u).astype(p.dtype)

        new = jax.tree.map(update, params, f32, m, v)
        return loss, new, m, v, g, raw

    params = make_params()
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    losses, first = [], None
    for t, b in enumerate(batches, start=1):
        loss, params, m, v, g, raw = step(
            params, m, v, jnp.float32(t), jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
        )
        losses.append(float(loss))
        if first is None:
            first = (g, [float(x) for x in raw])
        del g
    del m, v
    delta = change_norms(params, make_params())
    g = first[0]
    return Readings(
        losses, [float(x) for x in norms(g)], [float(x) for x in delta], first[1], g
    )


norms = jax.jit(leaf_norms)


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))


@jax.jit
def change_norms(params, params0) -> List[jax.Array]:
    """Norm of ``params - params0`` by leaf, in float32."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    return leaf_norms(jax.tree.map(lambda a, b: f32(a) - f32(b), params, params0))


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------
def _worst_leaf(side: Sequence[float], ref: Sequence[float], keep: Sequence[bool]) -> float:
    ref_kept = [r for r, k in zip(ref, keep) if k]
    floor = float(np.median(ref_kept))
    gaps = [abs(s - r) / max(r, floor) for s, r, k in zip(side, ref, keep) if k]
    return float(np.max(gaps)) if floor > 0 else float("inf")  # np.max keeps a NaN


def compare(side: Readings, ref: Readings) -> Dict[str, float]:
    """The numbers compared against the limits (see the module docstring)."""
    loss_gap = float(np.max([abs(a - b) / abs(b) for a, b in zip(side.losses, ref.losses)]))
    everyone = [True] * len(ref.grad_norms)
    median_raw = float(np.median(ref.raw_grad_norms))
    moving = [g >= STILL_LEAF * median_raw for g in ref.raw_grad_norms]
    diffs = [
        float(_diff_norm(jnp.asarray(a), b))
        for a, b in zip(jax.tree.leaves(side.first_grad), jax.tree.leaves(ref.first_grad))
    ]
    return {
        "loss_gap": loss_gap,
        "grad_gap": _worst_leaf(side.grad_norms, ref.grad_norms, everyone),
        "grad_diff": _worst_leaf(
            [r + d for r, d in zip(ref.grad_norms, diffs)], ref.grad_norms, everyone
        ),
        "update_gap": _worst_leaf(side.delta_norms, ref.delta_norms, moving),
    }
