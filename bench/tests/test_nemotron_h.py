"""The Nemotron-H model file and the program against it, at smoke sizes on
the CPU (kernels in interpret mode): the dropless MoE layer against the
dense held-expert reference under skewed routing, the expert shares summing
to the uncut layer, the whole training step against the reference, the
operation counts, and the readers of the cell's new metrics."""
import copy
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.tests.smoke import smoke_cell

CELL = "nemotron-3-nano-p1.bucket-prefetch"


def _conf(**changes):
    conf = copy.deepcopy(smoke_cell(CELL).conf)
    conf["train"]["param_dtype"] = "float32"
    deployment = changes.pop("expert_parallel", None)
    conf.update(changes)
    if deployment is not None:
        conf["deployment"] = dict(conf["deployment"], expert_parallel=deployment)
    return conf


MODEL = harness.load_module(harness.BENCH / "models" / "nemotron_h.py")


def _moe_params(conf, key=0):
    """The first MoE position's weights (period axis dropped) and an input."""
    params = MODEL.init_params(jax.random.PRNGKey(key), conf)
    p = jax.tree.map(lambda a: a[0], params["stack"]["pos0"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(key + 1), (2, 32, conf["hidden_size"]))
    return p, x


def test_dropless_layer_is_the_dense_held_expert_reference_under_skew():
    from repro.models import moe

    conf = _conf()
    cfg = MODEL.arch_config(conf)
    p, x = _moe_params(conf)
    D = MODEL.dims(conf)
    x_flat = x.reshape(-1, D["d"])
    # The seeded selection bias changes what is chosen at this size.
    no_bias = dict(p, router_bias=jnp.zeros_like(p["router_bias"]))
    assert not np.array_equal(moe.route(x_flat, p, cfg)[0], moe.route(x_flat, no_bias, cfg)[0])
    # Skew: held expert 1 is chosen by nearly every token.
    p = dict(p, router_bias=p["router_bias"].at[1].set(3.0))
    idx, _ = moe.route(x_flat, p, cfg)
    on_held = int(jnp.sum(idx < D["held"]))
    y, counters = moe.moe_layer(p, x, cfg)
    assert int(counters["moe_rows"]) == on_held  # dropless: every row on a held expert
    assert int(counters["moe_rows_max"]) == int(jnp.sum(idx == 1)) >= 0.9 * x_flat.shape[0]
    held_used = set(np.asarray(idx).ravel()) & set(range(D["held"]))
    assert int(counters["moe_experts"]) == len(held_used)
    want = MODEL._moe(p, x, D, conf, reference.matmul("fp32"))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


def test_the_shares_of_the_experts_sum_to_the_uncut_layer():
    """Four shares of 2 of 8 experts, the shared expert counted once, give
    what the uncut layer and the reference give for all 8."""
    from repro.models import moe

    whole = _conf(n_routed_experts=8, expert_parallel=1)
    share = _conf(n_routed_experts=2, expert_parallel=4)
    p, x = _moe_params(whole)
    x_flat = x.reshape(-1, whole["hidden_size"])
    cfg = MODEL.arch_config(share)
    total = moe.shared_expert(p, x_flat, cfg)
    for s in range(4):
        part = dict(p, w_up=p["w_up"][2 * s: 2 * s + 2], w_down=p["w_down"][2 * s: 2 * s + 2])
        total = total + moe.routed_experts(part, x_flat, cfg, e0=2 * s)[0]
    uncut, _ = moe.moe_layer(p, x, MODEL.arch_config(whole))
    np.testing.assert_allclose(total.reshape(x.shape), uncut, rtol=1e-5, atol=1e-5)
    want = MODEL._moe(p, x, MODEL.dims(whole), whole, reference.matmul("fp32"))
    np.testing.assert_allclose(uncut, want, rtol=1e-4, atol=1e-4)


def test_the_training_step_is_the_reference_step():
    """Loss, first gradient and the update of one step of the trainer's
    ``make_train_step`` (float32 weights) against the float32 reference."""
    from repro.launch.steps import make_train_step
    from repro.training.optimizer import OptSettings, adamw_init

    conf = _conf()
    tr = conf["train"]
    cfg = MODEL.arch_config(conf)
    settings = OptSettings(**{k: tr[k] for k in (
        "lr", "beta1", "beta2", "eps", "weight_decay", "grad_clip", "moment_dtype")})
    make = jax.jit(lambda key: MODEL.init_params(key, conf))
    key = jax.random.PRNGKey(7)
    rows = np.random.default_rng(7).integers(0, conf["vocab_size"], (2, 65)).astype(np.int32)
    batch = {"tokens": rows[:, :-1], "labels": rows[:, 1:]}

    params = make(key)
    (loss, counters), new, opt = jax.jit(make_train_step(cfg, settings))(
        params, adamw_init(params, settings), jax.tree.map(jnp.asarray, batch)
    )
    assert set(counters) == {"moe_rows", "moe_rows_max", "moe_experts"}
    ref = reference.train_reference(
        lambda p, t, l, mm: MODEL.loss(p, t, l, conf, mm), lambda: make(key), [batch],
        reference.Adam.from_train(tr), "fp32",
    )
    assert abs(float(loss) - ref.losses[0]) <= 1e-5 * ref.losses[0]
    grads = jax.tree.map(lambda m: m / (1 - tr["beta1"]), opt["m"])
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(ref.first_grad)):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * float(jnp.abs(want).max()))
    delta = [float(x) for x in reference.change_norms(new, make(key))]
    np.testing.assert_allclose(delta, ref.delta_norms, rtol=1e-3, atol=1e-9)


def test_the_selection_bias_balances_skewed_scores():
    """Scores with a shared slant across the experts, as random weights give:
    unbiased, the top experts take most choices; under ``balanced_bias``
    every expert takes its T k / E within 5 %."""
    T, E, k = 512, 16, 3
    noise = jax.random.normal(jax.random.PRNGKey(11), (T, E)) * 0.3
    scores = jax.nn.sigmoid(noise + jnp.linspace(-2.0, 2.0, E))

    def loads(bias):
        _, idx = jax.lax.top_k(scores + bias, k)
        return np.bincount(np.asarray(idx).ravel(), minlength=E)

    target = T * k / E
    assert loads(0.0).max() > 2 * target
    assert np.abs(loads(MODEL.balanced_bias(scores, k)) - target).max() <= 0.05 * target


def test_the_weights_carry_each_layers_balanced_bias():
    """``init_params`` sets each MoE layer's selection bias: nonzero,
    different by layer, and the same for the same key."""
    conf = _conf()
    a = MODEL.init_params(jax.random.PRNGKey(2), conf)["stack"]
    b = MODEL.init_params(jax.random.PRNGKey(2), conf)["stack"]
    biases = [a[pos]["mlp"]["router_bias"] for pos in sorted(a) if "mlp" in a[pos]]
    assert len(biases) == 3 and all(float(jnp.abs(x).max()) > 0 for x in biases)
    assert not np.array_equal(biases[0], biases[1])
    for pos in a:
        if "mlp" in a[pos]:
            np.testing.assert_array_equal(a[pos]["mlp"]["router_bias"], b[pos]["mlp"]["router_bias"])


def test_counts_by_hand_at_smoke_size():
    conf = copy.deepcopy(smoke_cell(CELL).conf)
    D = MODEL.dims(conf)
    # d 64, 4 heads of 16 (inner 64), 2 groups, state 16, conv 4, chunk 16;
    # 4 query and 2 KV heads of 16; experts 32 wide, 4 held of 64 routed,
    # top 3; shared 48; vocabulary 512; MEMEM*E: 3 M, 3 E, 1 *.
    assert (D["di"], D["E"], D["held"], D["ssm_layers"], D["moe_layers"], D["attn_layers"]) == (
        64, 64, 4, 3, 3, 1)
    mamba = 2 * (64 * (128 + 64 + 4) + 64 * 64) + 2 * 4 * 128 + (2 * 2 * 16 * 16 + 4 * 1536)
    attention = 2 * (64 * (64 + 64) + 64 * 64) + 2 * 2 * 64 * 32.5
    moe = 2 * 64 * 64 + (3 * 4 / 64) * 2 * 2 * 64 * 32 + 2 * 2 * 64 * 48
    forward = 3 * mamba + attention + 3 * moe + 2 * 64 * 512
    assert MODEL.train_flops_per_token(conf, 64) == pytest.approx(3 * forward, rel=1e-12)
    # 100 rows of one layer: 2*100*64*32 operations; rows in and out
    # 100*(64+32) and 3 experts' 64x32 weights, 2 bytes each (bf16 file).
    assert MODEL.gmm_kernel_cost(conf, 100, 3) == (2 * 100 * 64 * 32, (100 * 96 + 3 * 2048) * 2)
    assert MODEL.layout("MEMEM*E") == [("ssm", "moe"), ("ssm", "moe"), ("ssm", "none"),
                                       ("attn", "moe")]
    with pytest.raises(ValueError):
        MODEL.layout("EM")


def _run(counters, ops=None):
    cell = types.SimpleNamespace(model=MODEL, conf=copy.deepcopy(smoke_cell(CELL).conf))
    steps = [types.SimpleNamespace(counters=c) for c in counters]
    trace = types.SimpleNamespace(op_time=lambda match: (
        sum(n for name, (n, _) in (ops or {}).items() if match(name)),
        sum(s for name, (_, s) in (ops or {}).items() if match(name)),
    ))
    return types.SimpleNamespace(cell=cell, steps=steps, trace=trace,
                                 device={"kind": "TPU v5 lite"})


def test_readers_of_the_new_metrics():
    imbalance = harness.load_module(harness.BENCH / "metrics" / "expert_imbalance.py")
    gmm = harness.load_module(harness.BENCH / "metrics" / "gmm_roofline.py")
    tgmm = harness.load_module(harness.BENCH / "metrics" / "tgmm_roofline.py")
    steps = [{"moe_rows": 300, "moe_rows_max": 150, "moe_experts": 9},
             {"moe_rows": 300, "moe_rows_max": 75, "moe_experts": 12}]
    # 4 held experts: mean expert 75 rows; max 150 then 75.
    assert imbalance.read(_run(steps)) == pytest.approx(1.5)
    # A step whose held experts got no rows has no ratio; with no rows at all, no reading.
    empty = {"moe_rows": 0, "moe_rows_max": 0, "moe_experts": 0}
    assert imbalance.read(_run(steps + [empty])) == pytest.approx(1.5)
    assert imbalance.read(_run([empty, empty])) is None
    ops = {"gmm.3": (6, 1e-3), "tgmm.1": (2, 4e-3), "fusion.2": (1, 1.0)}
    run = _run(steps, ops)
    # 100 rows a call (600 rows over 2 steps and 3 layers); gmm reads the
    # 3.5 experts a call that have rows, tgmm writes all 4.
    for reader, calls, seconds, experts in ((gmm, 6, 1e-3, 3.5), (tgmm, 2, 4e-3, 4)):
        flops, nbytes = MODEL.gmm_kernel_cost(run.cell.conf, 100.0, experts)
        least = max(flops / 197e12, nbytes / 819e9)
        assert reader.read(run) == pytest.approx(100 * calls * least / seconds)
    # Steps without the counters (a program that has none): nothing to read.
    bare = _run([{}, {}], ops)
    assert imbalance.read(bare) is None and gmm.read(bare) is None and tgmm.read(bare) is None
