"""The layers a Nemotron-H model adds, against plain references on seeded
random inputs at small sizes (kernels in interpret mode): the grouped
matmul and its VJP, the flash-attention VJP, attention without RoPE, the
per-group gated norm, and the dropless MoE layer's routing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import gmm as G
from repro.kernels.ops import grouped_matmul
from repro.models import moe
from repro.models.config import ArchConfig
from repro.models.layers import (
    AttnMask,
    apply_rope,
    attention_block,
    chunked_attention,
    flash_attention_trainable,
    plain_attention,
    rms_norm,
)
from repro.models.ssm import gated_rms_norm

pytestmark = pytest.mark.slow  # interpret-mode kernels

TM = 8


def _rows_by_group(sizes, tm):
    starts = np.asarray(G.group_starts(jnp.asarray(sizes, jnp.int32), tm))
    return [(int(s), int(s) + n) for s, n in zip(starts, sizes)]


@pytest.mark.parametrize(
    "sizes", [(5, 0, 17, 8), (0, 0, 30, 0), (1, 1, 1, 1), (0, 0, 0, 0), (16, 8, 0, 3)],
    ids=["uneven", "one-group", "single-rows", "empty", "tile-aligned"],
)
@pytest.mark.parametrize("transpose_rhs", [False, True], ids=["rhs", "rhs-t"])
def test_grouped_matmul_against_a_loop_over_groups(sizes, transpose_rhs):
    """Forward and VJP against a jnp loop over each group's rows; rows that
    are no group's give zero and take no gradient."""
    M, K, N = 64, 16, 24
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(sum(sizes) + transpose_rhs), 3)
    lhs = jax.random.normal(k1, (M, K), jnp.float32)
    rhs = jax.random.normal(k2, (4, N, K) if transpose_rhs else (4, K, N), jnp.float32)
    cot = jax.random.normal(k3, (M, N), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    spans = _rows_by_group(sizes, TM)

    def weight(r):
        return r.T if transpose_rhs else r

    def kernel(lhs, rhs):
        if transpose_rhs:
            return G.gmm(lhs, rhs, group_sizes, tm=TM, transpose_rhs=True)
        return grouped_matmul(lhs, rhs, group_sizes, tm=TM)

    def loop(lhs, rhs):
        out = jnp.zeros((M, N), jnp.float32)
        for g, (a, b) in enumerate(spans):
            out = out.at[a:b].set(lhs[a:b] @ weight(rhs[g]))
        return out

    np.testing.assert_allclose(kernel(lhs, rhs), loop(lhs, rhs), rtol=1e-5, atol=1e-5)
    if transpose_rhs:
        return  # the transposed product is the forward's input gradient, checked below
    _, vjp = jax.vjp(kernel, lhs, rhs)
    _, vjp_ref = jax.vjp(loop, lhs, rhs)
    for got, want in zip(vjp(cot), vjp_ref(cot)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_tgmm_zeroes_empty_groups_and_ignores_padding_rows():
    sizes = jnp.asarray([3, 0, 9], jnp.int32)
    lhs = jnp.ones((32, 4), jnp.float32)  # padding rows hold ones too
    rhs = jnp.ones((32, 5), jnp.float32)
    out = G.tgmm(lhs, rhs, sizes, num_groups=3, out_dtype=jnp.float32, tm=TM)
    np.testing.assert_array_equal(out[:, 0, 0], [3.0, 0.0, 9.0])


@pytest.mark.parametrize("window", [None, 24], ids=["causal", "window"])
def test_flash_attention_vjp_is_chunked_attentions(window):
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    q, k, v, g = (
        jax.random.normal(key, shape, jnp.float32)
        for key, shape in zip(
            jax.random.split(jax.random.PRNGKey(0), 4),
            [(B, S, H, hd), (B, S, KV, hd), (B, S, KV, hd), (B, S, H, hd)],
        )
    )
    mask = AttnMask(causal=True, window=window)
    out, vjp = jax.vjp(lambda *a: flash_attention_trainable(*a, True, window, 16), q, k, v)
    ref, vjp_ref = jax.vjp(lambda *a: chunked_attention(*a, mask, chunk=16), q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    for got, want in zip(vjp(g), vjp_ref(g)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_attention_without_rope_attends_the_unrotated_heads(use_pallas):
    cfg = ArchConfig(
        name="t", family="dense", n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=0, vocab=8, dtype="float32", pos_emb="none", use_pallas=use_pallas,
    )
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    p = {
        "wq": jax.random.normal(keys[0], (32, 32)) / 6,
        "wk": jax.random.normal(keys[1], (32, 16)) / 6,
        "wv": jax.random.normal(keys[2], (32, 16)) / 6,
        "wo": jax.random.normal(keys[3], (32, 32)) / 6,
    }
    x = jax.random.normal(keys[4], (2, 16, 32))
    pos = jnp.arange(16)
    y, _ = attention_block(p, x, cfg, positions=pos)
    q = (x @ p["wq"]).reshape(2, 16, 4, 8)
    k = (x @ p["wk"]).reshape(2, 16, 2, 8)
    v = (x @ p["wv"]).reshape(2, 16, 2, 8)
    mask = AttnMask(causal=True)
    plain = plain_attention(q, k, v, mask).reshape(2, 16, 32) @ p["wo"]
    np.testing.assert_allclose(y, plain, rtol=1e-5, atol=1e-5)
    rotated = plain_attention(apply_rope(q, pos, 1e4), apply_rope(k, pos, 1e4), v, mask)
    assert not np.allclose(y, rotated.reshape(2, 16, 32) @ p["wo"], atol=1e-3)


def test_gated_norm_is_rms_norm_at_one_group_and_per_group_above():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 24), jnp.float32)
    scale = jax.random.normal(jax.random.PRNGKey(3), (24,), jnp.float32)
    one = jax.make_jaxpr(lambda a, s: gated_rms_norm(a, s, 1e-5, 1))(x, scale)
    plain = jax.make_jaxpr(lambda a, s: rms_norm(a, s, 1e-5))(x, scale)
    assert str(one) == str(plain)  # the same operations as the ungrouped norm
    got = gated_rms_norm(x, scale, 1e-5, 3)
    parts = [rms_norm(x[..., 8 * g: 8 * g + 8], scale[8 * g: 8 * g + 8], 1e-5) for g in range(3)]
    np.testing.assert_allclose(got, jnp.concatenate(parts, -1), rtol=1e-6, atol=1e-6)


MOE = ArchConfig(
    name="moe-t", family="moe", n_layers=1, d_model=16, n_heads=0, d_ff=24, vocab=8,
    period=("ssm",), mlp_pattern=("moe",), n_experts=8, top_k=3, capacity_factor=0.0,
    router="sigmoid_bias", routed_scale=2.5, experts_held=2, mlp_act="relu2", dtype="float32",
)


def test_dropless_rows_keep_every_assignment_on_a_held_expert():
    """Under routing skewed so that one held expert takes most rows, every
    (token, slot) on a held expert gets a row, in its expert's tiles."""
    T, k = 40, 3
    idx = jnp.stack([jnp.zeros(T, jnp.int32), jnp.full(T, 5), jnp.arange(T) % 8], 1)
    idx = idx.at[:, 2].set(jnp.where(idx[:, 2] == 0, 7, idx[:, 2]))
    w = jnp.arange(T * k, dtype=jnp.float32).reshape(T, k) + 1.0
    token, weight, sizes = moe._dropless_rows(idx, w, 0, 2, TM)
    want = [int(jnp.sum(idx == e)) for e in range(2)]
    assert sizes.tolist() == want and want[0] == T
    starts = np.asarray(G.group_starts(sizes, TM))
    for e in range(2):
        rows = slice(int(starts[e]), int(starts[e]) + want[e])
        t, c = np.asarray(token[rows]), np.asarray(weight[rows])
        assert sorted(t.tolist()) == sorted(np.nonzero(np.asarray(idx) == e)[0].tolist())
        np.testing.assert_array_equal(c, np.asarray(w)[t, np.argmax(np.asarray(idx)[t] == e, 1)])
    assert float(weight.sum()) == float(jnp.sum(jnp.where(idx < 2, w, 0)))


def test_selection_bias_chooses_and_scores_weigh():
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 16))
    p = {"router": jax.random.normal(jax.random.PRNGKey(5), (16, 8)) / 4,
         "router_bias": jnp.zeros(8)}
    idx0, w0 = moe.route(x, p, MOE)
    np.testing.assert_allclose(w0.sum(-1), 2.5, rtol=1e-6)
    biased = dict(p, router_bias=jnp.zeros(8).at[3].set(0.3))
    idx1, w1 = moe.route(x, biased, MOE)
    assert int(jnp.sum(idx1 == 3)) > int(jnp.sum(idx0 == 3))
    # The bias chooses; the weights are the chosen unbiased scores, normalised.
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen = jnp.take_along_axis(scores, idx1, -1)
    np.testing.assert_allclose(w1, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def test_dense_mlp_relu2():
    cfg = dataclasses.replace(MOE, mlp_pattern=("mlp",), n_experts=0, top_k=0, experts_held=0,
                              capacity_factor=1.25)
    from repro.models.layers import mlp_block

    p = {"w_up": jnp.eye(16, 24), "w_down": jnp.eye(24, 16)}
    x = jnp.linspace(-1, 1, 32).reshape(1, 2, 16)
    np.testing.assert_allclose(mlp_block(p, x, cfg), jnp.square(jax.nn.relu(x)), rtol=1e-6)


@pytest.mark.parametrize(
    "changes",
    [dict(capacity_factor=1.25, experts_held=0), dict(capacity_factor=1.25, router="softmax",
     experts_held=0), dict(capacity_factor=1.25, router="softmax", routed_scale=1.0,
     experts_held=0, shared_expert_ff=8), dict(router="softmax"), dict(mlp_act="swiglu")],
    ids=["capacity-sigmoid", "capacity-scaled", "capacity-shared", "dropless-softmax",
         "dropless-swiglu"],
)
def test_each_moe_layer_refuses_the_other_layers_knobs(changes):
    """The capacity layer reads none of the dropless layer's knobs, and the
    dropless layer routes only by sigmoid scores to relu2 experts."""
    with pytest.raises(ValueError):
        dataclasses.replace(MOE, **changes)
