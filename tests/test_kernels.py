"""Pallas kernel validation: interpret=True on CPU, shape/dtype sweeps,
assert_allclose against the pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy; excluded from the smoke lane

from repro.kernels import ops, ref

_ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(key, B, Sq, Sk, H, KV, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, hd), jnp.float32).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KV,hd,block",
    [
        (1, 128, 4, 4, 64, 64),   # MHA, one block row
        (2, 256, 8, 2, 32, 64),   # GQA 4:1
        (1, 384, 6, 1, 16, 128),  # MQA, uneven blocks (384 = 3x128)
        (2, 96, 4, 2, 64, 32),    # small seq, multiple blocks
    ],
)
def test_flash_attention_causal(B, S, H, KV, hd, block, dtype):
    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, S, H, KV, hd, dtype)
    out = ops.flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True
    )
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=_ATOL[dtype], rtol=_ATOL[dtype],
    )


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_attention_sliding_window(window):
    B, S, H, KV, hd = 2, 256, 4, 2, 32
    q, k, v = _qkv(jax.random.PRNGKey(1), B, S, S, H, KV, hd, jnp.float32)
    out = ops.flash_attention(
        q, k, v, causal=True, window=window, block_q=64, block_k=64, interpret=True
    )
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-5, rtol=2e-5
    )


def test_flash_attention_padded_seq():
    """Sq not a block multiple exercises the pad/mask path."""
    B, S, H, KV, hd = 1, 200, 4, 4, 32
    q, k, v = _qkv(jax.random.PRNGKey(2), B, S, S, H, KV, hd, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-5, rtol=2e-5
    )


def test_flash_attention_noncausal_encoder():
    B, S, H, KV, hd = 2, 128, 4, 4, 64
    q, k, v = _qkv(jax.random.PRNGKey(3), B, S, S, H, KV, hd, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------
def _ssd_inputs(key, B, S, H, P, G, N, dtype):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.float32)
    D = jnp.ones((H,), jnp.float32)
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,G,N,chunk",
    [
        (1, 64, 2, 16, 1, 16, 16),   # minimal
        (2, 128, 4, 32, 2, 16, 32),  # grouped B/C
        (1, 96, 3, 16, 1, 32, 32),   # odd head count, 3 chunks
    ],
)
def test_ssd_scan(B, S, H, P, G, N, chunk, dtype):
    x, dt, A, Bm, Cm, D = _ssd_inputs(jax.random.PRNGKey(4), B, S, H, P, G, N, dtype)
    y, st = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    y_ref, st_ref = ref.ssd_scan(x, dt, A, Bm, Cm, D)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), atol=tol, rtol=tol
    )
    np.testing.assert_allclose(
        np.asarray(st, np.float32), np.asarray(st_ref, np.float32), atol=tol, rtol=tol
    )


def test_ssd_kernel_matches_model_chunked_path():
    """Kernel vs the model's XLA chunked implementation (not just the
    sequential oracle) — the two production paths must agree."""
    from repro.models.ssm import ssd_chunked

    x, dt, A, Bm, Cm, D = _ssd_inputs(jax.random.PRNGKey(5), 2, 128, 4, 32, 2, 16, jnp.float32)
    y_k, st_k = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=32, interpret=True)
    y_m, st_m = ssd_chunked(x, dt, A, Bm, Cm, D, chunk=32)
    np.testing.assert_allclose(
        np.asarray(y_k, np.float32), np.asarray(y_m, np.float32), atol=1e-4, rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(st_k, np.float32), np.asarray(st_m, np.float32), atol=1e-4, rtol=1e-4
    )


def test_model_forward_with_pallas_kernels_matches_xla():
    """End-to-end: a reduced hybrid model (attention + SSD layers) with
    use_pallas=True must match the XLA reference path."""
    import dataclasses

    from repro import configs
    from repro.models import model as M

    base = configs.reduce_for_smoke(configs.get("jamba-1.5-large-398b"))
    base = dataclasses.replace(base, dtype="float32", capacity_factor=16.0)
    kcfg = dataclasses.replace(base, use_pallas=True)
    key = jax.random.PRNGKey(7)
    params = M.init_params(key, base)
    batch = {"tokens": jax.random.randint(key, (2, 64), 0, base.vocab)}
    h_x, _ = M.forward(params, base, batch, remat=False)
    h_k, _ = M.forward(params, kcfg, batch, remat=False)
    np.testing.assert_allclose(
        np.asarray(h_x, np.float32), np.asarray(h_k, np.float32), atol=2e-3, rtol=2e-3
    )


def test_ssd_chunked_gradients_finite_at_full_chunk():
    """Above the diagonal a 128-step chunk's decay sums overflow exp; the
    mask must act before exp or the backward pass turns 0 * inf into NaN."""
    from repro.models.ssm import ssd_chunked

    x, dt, A, Bm, Cm, D = _ssd_inputs(jax.random.PRNGKey(6), 1, 256, 2, 16, 1, 16, jnp.float32)
    dt = dt + 1.0  # decays of ~1.7 per step: sums reach ~-200 in a chunk

    def loss(x, dt):
        y, st = ssd_chunked(x, dt, A, Bm, Cm, D, chunk=128)
        return y.sum() + st.sum()

    for g in jax.grad(loss, argnums=(0, 1))(x, dt):
        assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_gradients_match_xla_chunked(dtype):
    """The kernel's custom VJP (backward through ssd_chunked) gives the
    gradients of the XLA path."""
    from repro.models.ssm import ssd_chunked, ssd_pallas

    x, dt, A, Bm, Cm, D = _ssd_inputs(jax.random.PRNGKey(8), 2, 64, 2, 16, 1, 16, dtype)
    Bm, Cm = Bm.astype(dtype), Cm.astype(dtype)

    def loss(f):
        def inner(x, dt, A, Bm, Cm, D):
            y, st = f(x, dt, A, Bm, Cm, D)
            return y.astype(jnp.float32).sum() + st.sum()
        return inner

    args = (x, dt, A, Bm, Cm, D)
    g_k = jax.grad(loss(lambda *a: ssd_pallas(*a, 16)), argnums=range(6))(*args)
    g_x = jax.grad(loss(lambda *a: ssd_chunked(*a, chunk=16)), argnums=range(6))(*args)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    for a, b in zip(g_k, g_x):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=tol, rtol=tol
        )
