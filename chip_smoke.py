#!/usr/bin/env python3
"""Bring the DELI-fed trainer up on a TPU and check what it computes.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded data-parallel step

One chip runs five phases in this one process:

1. device: JAX must report a TPU (no CPU fallback, no interpret mode);
2. train: ``repro.launch.train.train`` trains mamba2-130m at its published
   widths (SSD Pallas kernel on) for ``TRAIN_STEPS`` steps at seq 2048,
   batch 8, fed through ``DeliLoader``; every loss finite, the last five
   below the first five, one compile of the step, ``tpu_custom_call`` in it;
3. kernel against reference: the loss of one fixed batch with the Pallas
   SSD kernel and with the XLA chunked scan agree within ``KERNEL_RTOL``;
4. checkpoint: a fresh trainer restores the last checkpoint through
   ``try_restore``; params and optimizer state are bitwise equal, and its
   next step's loss equals the uninterrupted run's;
5. the last line of stdout is the JSON result.

``--chips 4`` runs only the data-parallel phase: the sharded step
(``step_for_cell`` + ``ShardingRules(fsdp_axes=("data",))``) on a
(data=4, model=1) mesh against the one-device step, on the same batches.

Any failed check exits non-zero before the result line is printed.  Step
times are host clock around ``jax.block_until_ready``.  The persistent
compilation cache is on (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import statistics
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.training import checkpoint as ckpt  # noqa: E402

ARCH = "mamba2-130m"
SEQ_LEN = 2048  # Mamba-2's training context (arXiv:2405.21060)
BATCH = 8
TRAIN_STEPS = 20  # checkpoint_every is 10: the last step is checkpointed
PARALLEL_STEPS = 4

BF16_EPS = 2.0 ** -8
#: |loss_kernel - loss_xla| / |loss_xla|: both paths stream x, B, C and y in
#: bf16 and keep the decay chain and state in f32, so they differ by
#: rounding only; four bf16 ulps of the loss bounds that.
KERNEL_RTOL = 4 * BF16_EPS
#: Per-step |loss_sharded - loss_one_device| / |loss_one_device|: the same
#: arithmetic with gradients summed in another order, which moves bf16
#: parameter roundings after the first update.
SHARDED_RTOL = 4 * BF16_EPS


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


def model_config():
    """mamba2-130m at its published widths and dtype, SSD kernel on."""
    return dataclasses.replace(configs.get(ARCH), use_pallas=True)


def phase_device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)} jax={jax.__version__}")
    check(d.platform == "tpu", f"JAX finds no TPU (platform {d.platform!r})")
    check(len(devices) >= chips, f"{chips} chips asked, {len(devices)} found")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def check_kernel_compiled(step_text: str) -> None:
    n = step_text.count("tpu_custom_call")
    print(f"compiled step: {n} tpu_custom_call site(s)")
    check(n > 0, "the compiled step holds no tpu_custom_call")


def compiled_step_text(trainer) -> str:
    import jax
    import jax.numpy as jnp

    tokens = jax.ShapeDtypeStruct(
        (trainer.tcfg.batch_size, trainer.tcfg.seq_len), jnp.int32
    )
    lowered = trainer._step_fn.lower(
        trainer.params, trainer.opt_state, {"tokens": tokens, "labels": tokens}
    )
    return lowered.compile().as_text()


def phase_train(cfg, ckpt_dir: str):
    print(f"train: {cfg.name} params={cfg.param_count()} dtype={cfg.dtype} "
          f"use_pallas={cfg.use_pallas} seq_len={SEQ_LEN} batch={BATCH} "
          f"steps={TRAIN_STEPS} loader=DeliLoader")
    trainer = train(
        cfg, steps=TRAIN_STEPS, seq_len=SEQ_LEN, batch=BATCH,
        ckpt_dir=ckpt_dir, log_every=10 ** 9,
    )
    ms = trainer.metrics
    for m in ms:
        print(f"step {m.step} loss {m.loss!r} data_wait_s {m.data_wait_s!r} "
              f"step_s {m.compute_s!r} hits {m.hits} misses {m.misses}")
    losses = [m.loss for m in ms]
    check(len(losses) == TRAIN_STEPS, f"{len(losses)} of {TRAIN_STEPS} steps ran")
    check(all(math.isfinite(x) for x in losses), "a loss is not finite")
    first, last = statistics.fmean(losses[:5]), statistics.fmean(losses[-5:])
    print(f"loss: mean of first 5 {first!r}, of last 5 {last!r}")
    check(last < first, "the loss did not fall")
    compiles = trainer._step_fn._cache_size()
    print(f"step compiles: {compiles}")
    check(compiles == 1, f"the step compiled {compiles} times")
    step_s = [m.compute_s for m in ms]
    steady = statistics.median(step_s[1:])
    print(f"first step (trace + compile + run) s: {step_s[0]!r}")
    print(f"compile s (first step - steady step): {step_s[0] - steady!r}")
    print(f"steady step s (median of steps 2..{TRAIN_STEPS}): {steady!r}")
    return trainer


def fixed_batch(cfg, seed: int = 1):
    import jax.numpy as jnp

    from repro.data.synthetic import make_lm_payloads

    rows = make_lm_payloads(BATCH, SEQ_LEN, cfg.vocab, seed=seed)
    tokens = np.stack([np.frombuffer(rows[i], np.int32) for i in range(BATCH)])
    return {"tokens": jnp.asarray(tokens[:, :-1]), "labels": jnp.asarray(tokens[:, 1:])}


def phase_kernel_vs_reference(trainer) -> None:
    import jax

    from repro.models import model as M

    cfg = trainer.cfg
    batch = fixed_batch(cfg)
    loss_fn = jax.jit(M.train_loss, static_argnums=1)
    kernel = float(loss_fn(trainer.params, cfg, batch))
    xla = float(loss_fn(trainer.params, dataclasses.replace(cfg, use_pallas=False), batch))
    rel = abs(kernel - xla) / abs(xla)
    print(f"kernel vs reference: loss pallas {kernel!r} xla {xla!r} "
          f"rel diff {rel!r} (tolerance {KERNEL_RTOL!r})")
    check(math.isfinite(kernel) and rel <= KERNEL_RTOL,
          "Pallas and XLA losses disagree")


def _host_leaves(tree):
    import jax

    # Copies: the trainer's next step takes the device buffers over.
    return [np.array(x, copy=True) for x in jax.tree.leaves(tree)]


def _bitwise_equal(a, b) -> bool:
    return len(a) == len(b) and all(
        x.dtype == y.dtype and x.shape == y.shape and x.tobytes() == y.tobytes()
        for x, y in zip(a, b)
    )


def phase_checkpoint(trainer, ckpt_dir: str) -> None:
    saved = ckpt.latest_step(ckpt_dir)
    check(saved == trainer.step, f"last checkpoint is step {saved}, not {trainer.step}")
    params, opt = _host_leaves(trainer.params), _host_leaves(trainer.opt_state)
    with trainer.loader.service:
        trainer.train(1)  # the uninterrupted run's next step
    fresh = train(
        trainer.cfg, steps=0, seq_len=trainer.tcfg.seq_len,
        batch=trainer.tcfg.batch_size, ckpt_dir=ckpt_dir, resume=True,
    )
    check(fresh.step == saved, f"restored step {fresh.step}, saved {saved}")
    check(_bitwise_equal(_host_leaves(fresh.params), params), "restored params differ")
    check(_bitwise_equal(_host_leaves(fresh.opt_state), opt), "restored optimizer differs")
    with fresh.loader.service:
        fresh.train(1)
    a, b = trainer.metrics[-1].loss, fresh.metrics[-1].loss
    print(f"checkpoint: step {saved} restored bitwise; next loss "
          f"uninterrupted {a!r} resumed {b!r}")
    check(a == b, "the resumed step's loss differs from the uninterrupted run's")


def run_one_chip() -> None:
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        trainer = phase_train(model_config(), ckpt_dir)
        check_kernel_compiled(compiled_step_text(trainer))
        phase_kernel_vs_reference(trainer)
        phase_checkpoint(trainer, ckpt_dir)


def _state_bytes_per_device(trees) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(trees):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def phase_data_parallel(chips: int) -> None:
    """The sharded step on (data=chips, model=1) against one device.

    Both run the XLA SSD path: GSPMD cannot partition a Pallas call.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import ShardingRules, input_shardings, param_shardings
    from repro.launch.mesh import make_smoke_mesh
    from repro.launch.steps import make_train_step, step_for_cell
    from repro.models import model as M
    from repro.models.config import ShapeConfig

    cfg = dataclasses.replace(model_config(), use_pallas=False)
    print(f"data parallel: {cfg.name} on (data={chips}, model=1), global batch "
          f"{BATCH}, seq_len {SEQ_LEN}, {PARALLEL_STEPS} steps, XLA SSD path")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        ref = train(cfg, steps=0, seq_len=SEQ_LEN, batch=BATCH, ckpt_dir=ckpt_dir)
    batches = []
    with ref.loader.service:
        for batch in ref.loader:
            batches.append(ref._to_device_batch(batch))
            if len(batches) == PARALLEL_STEPS:
                break
    params0, opt0 = ref.params, ref.opt_state

    # The trainer's own step takes its arguments over; this one keeps
    # params0 and opt0 for the sharded run.
    one_step = jax.jit(make_train_step(cfg, ref.settings))
    one_losses = []
    params, opt = params0, opt0
    for b in batches:
        (loss, _), params, opt = jax.block_until_ready(one_step(params, opt, b))
        one_losses.append(float(loss))
    del params, opt

    mesh = make_smoke_mesh(chips, 1)
    rules = ShardingRules(mesh, fsdp_axes=("data",))
    pshard = param_shardings(rules, cfg, M.param_shapes(cfg))
    oshard = {"m": pshard, "v": pshard, "step": NamedSharding(mesh, P())}
    bshard = input_shardings(rules, cfg, batches[0])
    step, _, _ = step_for_cell(
        cfg, ShapeConfig("train_smoke", SEQ_LEN, BATCH, "train"), rules,
        settings=ref.settings, microbatches=1,
    )
    sharded_step = jax.jit(
        step,
        in_shardings=(pshard, oshard, bshard),
        out_shardings=(NamedSharding(mesh, P()), pshard, oshard),
    )
    params, opt = jax.device_put(params0, pshard), jax.device_put(opt0, oshard)
    del params0, opt0
    ref.params = ref.opt_state = None  # only the sharded copy stays
    losses, times = [], []
    with mesh:
        for b in batches:
            t0 = time.monotonic()
            (loss, _), params, opt = jax.block_until_ready(
                sharded_step(params, opt, jax.device_put(b, bshard))
            )
            times.append(time.monotonic() - t0)
            losses.append(float(loss))

    total = sum(x.nbytes for x in jax.tree.leaves((params, opt)))
    held = _state_bytes_per_device((params, opt))
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        print(f"device {d.id}: params+opt shard bytes {held.get(d, 0)} "
              f"bytes_in_use {stats.get('bytes_in_use')}")
    print(f"params+opt total bytes {total}")
    for i, (a, b) in enumerate(zip(one_losses, losses), 1):
        print(f"step {i} loss one-device {a!r} sharded {b!r} "
              f"rel diff {abs(a - b) / abs(a)!r} step_s {times[i - 1]!r}")
    check(sharded_step._cache_size() == 1, "the sharded step compiled more than once")
    check(all(math.isfinite(x) for x in losses), "a sharded loss is not finite")
    check(
        all(abs(a - b) <= SHARDED_RTOL * abs(a) for a, b in zip(one_losses, losses)),
        f"sharded losses leave the tolerance {SHARDED_RTOL!r}",
    )
    check(
        len(held) == chips and max(held.values()) < 0.5 * total,
        "parameters and optimizer state are not spread over the devices",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded data-parallel phase")
    args = ap.parse_args(argv)
    device = phase_device(args.chips)
    print(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        phase_data_parallel(args.chips)
    else:
        run_one_chip()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
