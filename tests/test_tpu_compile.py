"""Ahead-of-time compiles for a described TPU v5e chip: the kernels of the
main path at real widths and the full-width mamba2-130m train step with
the SSD kernel on.  Nothing runs; the TPU compiler refuses here what it
would refuse on the chip (block tiling, unsupported primitives, memory).

The topology is described inside a module-scoped fixture, never at import,
so every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU library.  The persistent compilation cache
is off here: entries compiled for a described chip cannot be read back.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy; excluded from the smoke lane

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            from jax.experimental import topologies

            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def on_chip(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return struct


def test_ssd_kernel_compiles_at_mamba2_130m_widths(on_chip):
    from repro.kernels.ssd import ssd_scan

    B, S, H, P, N = 8, 2048, 24, 64, 128
    args = (
        on_chip((B, S, H, P), jnp.bfloat16),
        on_chip((B, S, H), jnp.float32),
        on_chip((H,), jnp.float32),
        on_chip((B, S, 1, N), jnp.bfloat16),
        on_chip((B, S, 1, N), jnp.bfloat16),
        on_chip((H,), jnp.float32),
    )
    step = jax.jit(lambda *a: ssd_scan(*a, chunk=128, interpret=False))
    assert "tpu_custom_call" in step.lower(*args).compile().as_text()


def test_flash_attention_compiles_at_internlm2_20b_widths(on_chip):
    from repro.kernels.flash_attention import flash_attention

    q = on_chip((1, 2048, 48, 128), jnp.bfloat16)
    kv = on_chip((1, 2048, 8, 128), jnp.bfloat16)
    step = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
    assert "tpu_custom_call" in step.lower(q, kv, kv).compile().as_text()


def test_full_mamba2_130m_train_step_with_kernel_fits_one_chip(on_chip, monkeypatch):
    from repro import configs
    from repro.kernels import ops
    from repro.launch.steps import make_train_step
    from repro.models import model as M
    from repro.training.optimizer import OptSettings, opt_state_shapes

    # ops picks interpret mode from the default backend, the CPU here.
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(configs.get("mamba2-130m"), use_pallas=True)
    settings = OptSettings.auto(cfg.param_count())

    def place(tree):
        return jax.tree.map(lambda l: on_chip(l.shape, l.dtype), tree)

    params = M.param_shapes(cfg)
    batch = {k: on_chip((8, 2048), jnp.int32) for k in ("tokens", "labels")}
    compiled = (
        jax.jit(make_train_step(cfg, settings))
        .lower(place(params), place(opt_state_shapes(params, settings)), batch)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert used < HBM_BYTES, used
