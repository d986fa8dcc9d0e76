"""Training launcher: ``--arch <id>`` selects any assigned architecture.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --smoke --steps 30

:func:`train` is the entry point behind the CLI: it builds the LM data
plane (``make_lm_spec`` -> ``build_runtime(clock=RealClock())`` -> this
rank's ``DeliLoader`` and threaded ``PrefetchService``), the ``Trainer``,
trains, and returns the trainer.  ``chip_smoke.py`` calls the same function
on the chip at full width.  Models above 1B parameters need the pod
runtime; their full configs are exercised via the dry-run
(launch/dryrun.py).  On a pod this driver is launched once per host: each
process feeds its local devices from its own DELI pipeline (rank/world
partition the sample space).
"""
from __future__ import annotations

import argparse
import tempfile
from typing import Optional

from repro import configs
from repro.core import PrefetchConfig, RealClock
from repro.data import decode_tokens, make_lm_spec
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ArchConfig
from repro.training.loop import Trainer, TrainerConfig
from repro.training.optimizer import OptSettings


def train(
    cfg: ArchConfig,
    *,
    steps: int,
    seq_len: int,
    batch: int,
    cache: int = 256,
    rank: int = 0,
    world: int = 1,
    ckpt_dir: Optional[str] = None,
    resume: bool = False,
    log_every: int = 10,
) -> Trainer:
    """Train ``cfg`` for ``steps`` steps on the DELI-fed LM stream.

    The returned trainer keeps its loader; ``with trainer.loader.service:
    trainer.train(n)`` continues the same run.  With ``resume`` the trainer
    first restores the newest checkpoint under ``ckpt_dir``.
    """
    spec = make_lm_spec(
        n_samples=max(1024, batch * 64),
        seq_len=seq_len,
        vocab=cfg.vocab,
        batch_size=batch,
        cache_items=cache,
        world=world,
        policy=PrefetchConfig.fifty_fifty(cache),
    )
    cluster = spec.build_runtime(clock=RealClock())
    loader, service = cluster.loaders[rank], cluster.services[rank]
    trainer = Trainer(
        cfg,
        loader,
        TrainerConfig(
            seq_len=seq_len,
            batch_size=batch,
            checkpoint_dir=ckpt_dir or tempfile.mkdtemp(prefix="deli_"),
            checkpoint_every=max(10, steps // 3),
            log_every=log_every,
        ),
        decode_fn=decode_tokens,
        settings=OptSettings.auto(cfg.param_count()),
    )
    if resume and trainer.try_restore():
        print(f"resumed from step {trainer.step}")
    with service:
        trainer.train(steps)
    return trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache", type=int, default=256)
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--world", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = configs.reduce_for_smoke(cfg)
    elif cfg.param_count() > 1e9:
        raise SystemExit(
            f"{args.arch} has {cfg.param_count()/1e9:.0f}B params — full-size "
            "training needs the pod runtime; use --smoke here, or "
            "launch/dryrun.py to compile the full config."
        )
    if cfg.frontend == "frame":
        raise SystemExit("audio encoder training uses precomputed frame "
                         "embeds; see tests/test_arch_smoke.py for the path")
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M")
    print(f"compile cache: {enable_compile_cache()}")

    trainer = train(
        cfg,
        steps=args.steps,
        seq_len=args.seq_len,
        batch=args.batch,
        cache=args.cache,
        rank=args.rank,
        world=args.world,
        ckpt_dir=args.ckpt_dir,
        resume=args.resume,
    )
    metrics = trainer.metrics
    wait = sum(m.data_wait_s for m in metrics)
    prepare = sum(m.prepare_s for m in metrics)
    dispatch = sum(m.dispatch_s for m in metrics)
    comp = sum(m.compute_s for m in metrics)
    print(
        f"done: step {trainer.step} loss {metrics[-1].loss:.4f} | "
        f"data-wait {wait:.2f}s, prepare {prepare:.2f}s, dispatch {dispatch:.2f}s, "
        f"device step {comp:.1f}s"
    )


if __name__ == "__main__":
    main()
