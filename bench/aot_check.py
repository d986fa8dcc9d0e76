#!/usr/bin/env python3
"""Compile each configuration's training step for a described TPU v5e.

    JAX_PLATFORMS=cpu python bench/aot_check.py [--batch N] [config ...]

Without names it compiles every configuration of ``BENCHMARK.json``.

The step is the trainer's own, jitted as ``Trainer`` jits it (no donated
arguments), over the benchmark's weight tree, fp32 moments and a
``(batch, seq_len)`` token batch on one chip of a ``v5e:2x2`` topology that
the TPU compiler describes without a chip attached.  It prints the
compiler's memory analysis, the bytes that decide whether a batch fits the
chip's 16 GB.  Run by hand; nothing here runs on a device.  Off the chip the
program routes Pallas kernels to interpret mode, so the SSD forward
compiles as XLA operations here.
"""
import argparse
import functools
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("configs", nargs="*", help="configuration names (default: every one)")
    ap.add_argument("--batch", type=int, help="batch to compile (default: the file's)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.launch.steps import make_train_step
    from repro.training.optimizer import OptSettings, opt_state_shapes

    from bench.harness import load_json, load_module

    names = args.configs or [c["name"] for c in load_json(ROOT / "BENCHMARK.json")["configs"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        conf = load_json(ROOT / "bench" / "configs" / f"{name}.json")
        model = load_module(ROOT / "bench" / "models" / f"{conf['model_type']}.py")
        tr = conf["train"]
        batch = args.batch or tr["batch"]
        cfg = model.arch_config(conf)
        settings = OptSettings(
            lr=tr["lr"], beta1=tr["beta1"], beta2=tr["beta2"], eps=tr["eps"],
            weight_decay=tr["weight_decay"], grad_clip=tr["grad_clip"],
            moment_dtype=tr["moment_dtype"],
        )
        on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)  # noqa: E731
        params = jax.tree.map(
            on_chip,
            jax.eval_shape(functools.partial(model.init_params, jax.random.PRNGKey(0), conf)),
        )
        opt = jax.tree.map(on_chip, opt_state_shapes(params, settings))
        tokens = jax.ShapeDtypeStruct((batch, tr["seq_len"]), jnp.int32, sharding=chip)
        step = jax.jit(make_train_step(cfg, settings))
        compiled = step.lower(params, opt, {"tokens": tokens, "labels": tokens}).compile()
        mem = compiled.memory_analysis()
        n_params = sum(x.size for x in jax.tree.leaves(params))
        total = (
            mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes
        )
        print(
            f"{name} batch {batch} seq {tr['seq_len']}: params {n_params} | "
            f"arguments {mem.argument_size_in_bytes} B, outputs {mem.output_size_in_bytes} B, "
            f"temporaries {mem.temp_size_in_bytes} B, aliased {mem.alias_size_in_bytes} B, "
            f"generated code {mem.generated_code_size_in_bytes} B | "
            f"held at the step's peak {total} B"
        )


if __name__ == "__main__":
    main()
