"""The training loop: DELI data plane -> device arrays -> jit'd train step,
with the paper's data-wait accounting at STEP granularity, step-atomic
async checkpointing, restart recovery, and elastic re-partitioning.

This is where the paper's mechanism meets the TPU training stack: the
loader's miss/wait metrics decide whether the input pipeline (not the mesh)
is the bottleneck, exactly the measurement DELI §V makes — but per training
step instead of per epoch, because a pod-scale job wants to see data-wait
within the step budget, not after an epoch is lost.

Each step is a profiler step span ``train.step`` (its ``step_num`` is the
step), and each host phase of it a span inside: ``train.next_batch`` (the
request to the loader), ``train.prepare`` (decode, stack, the copy to the
device), ``train.dispatch`` (the call of the jitted step), ``train.block``
(waiting for its result) and ``train.read_loss``.  They land in the same
trace as the device's operations, on its clock, whenever a profiler trace
runs, and cost a few microseconds each when none does.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.loader import Batch, DeliLoader
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.training import checkpoint as ckpt
from repro.training.optimizer import OptSettings, adamw_init
from repro.launch.steps import make_train_step


@dataclasses.dataclass
class StepMetrics:
    step: int
    loss: float
    data_wait_s: float
    compute_s: float
    hits: int
    misses: int
    prepare_s: float  # the train.prepare span: decode, stack, copy to the device
    dispatch_s: float  # the train.dispatch span: the jitted step's call until it returns


@contextlib.contextmanager
def _phase(name: str, seconds: Dict[str, float]) -> Iterator[None]:
    """A profiler span ``name`` around the block, whose host duration goes
    to ``seconds[name]``: a span and its counter cannot drift apart."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    seconds[name] = time.perf_counter() - t0


@dataclasses.dataclass
class TrainerConfig:
    seq_len: int
    batch_size: int  # per-host samples per step
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    log_every: int = 10


class Trainer:
    """Single-host driver (CPU container); the same step/ckpt code paths the
    pod launcher uses, minus the multi-process runtime."""

    def __init__(
        self,
        cfg: ArchConfig,
        loader: DeliLoader,
        tcfg: TrainerConfig,
        decode_fn: Callable[[bytes], np.ndarray],
        settings: Optional[OptSettings] = None,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.loader = loader
        self.tcfg = tcfg
        self.decode_fn = decode_fn
        self.settings = settings or OptSettings.auto(cfg.param_count())
        self.params = M.init_params(jax.random.PRNGKey(seed), cfg)
        self.opt_state = adamw_init(self.params, self.settings)
        self.step = 0
        self.metrics: List[StepMetrics] = []
        self._step_fn = jax.jit(make_train_step(cfg, self.settings))
        self._ckpt = (
            ckpt.AsyncCheckpointer(tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints)
            if tcfg.checkpoint_dir
            else None
        )

    # -- data ----------------------------------------------------------------
    def _to_device_batch(self, batch: Batch) -> Dict[str, jax.Array]:
        tokens = batch.stacked(self.decode_fn).astype(np.int32)
        tokens = tokens[:, : self.tcfg.seq_len + 1]
        return {
            "tokens": jnp.asarray(tokens[:, :-1]),
            "labels": jnp.asarray(tokens[:, 1:]),
        }

    # -- checkpoint/restore ----------------------------------------------------
    def try_restore(self) -> bool:
        if not self.tcfg.checkpoint_dir:
            return False
        step = ckpt.latest_step(self.tcfg.checkpoint_dir)
        if step is None:
            return False
        like = (
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.params),
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.opt_state),
        )
        params, opt, loader_state, extra = ckpt.restore_checkpoint(
            self.tcfg.checkpoint_dir, step, like=like
        )
        self.params, self.opt_state = params, opt
        self.step = int(extra.get("step", step))
        if loader_state:
            self.loader.load_state_dict(loader_state)
        return True

    def _maybe_checkpoint(self):
        if self._ckpt and self.step % self.tcfg.checkpoint_every == 0:
            self._ckpt.save(
                self.step,
                self.params,
                self.opt_state,
                loader_state=self.loader.state_dict(),
                extra={"step": self.step},
            )

    # -- the loop ---------------------------------------------------------------
    def train(self, num_steps: int, epochs: int = 10_000) -> List[StepMetrics]:
        target = self.step + num_steps
        epoch = self.loader.state_dict()["epoch"]
        while self.step < target and epoch < epochs:
            self.loader.set_epoch(epoch)
            batches = iter(self.loader)
            while self._train_step(batches) and self.step < target:
                pass
            epoch += 1
        if self._ckpt:
            self._ckpt.wait()
        return self.metrics

    def _train_step(self, batches: Iterator[Batch]) -> bool:
        """One step from the loader's next batch; False where there is none.

        ``compute_s`` runs from the call of the jitted step to the end of
        the loss read: the device step as the host sees it.  A request that
        finds no batch still lies in a ``train.step`` span, since the span
        opens before the request and a profiler span cannot be withdrawn.
        """
        seconds: Dict[str, float] = {}
        with jax.profiler.StepTraceAnnotation("train.step", step_num=self.step + 1):
            with _phase("train.next_batch", seconds):
                batch = next(batches, None)
            if batch is None:
                return False
            with _phase("train.prepare", seconds):
                dev_batch = self._to_device_batch(batch)
            t0 = time.perf_counter()
            with _phase("train.dispatch", seconds):
                out = self._step_fn(self.params, self.opt_state, dev_batch)
            with _phase("train.block", seconds):
                loss, self.params, self.opt_state = jax.block_until_ready(out)
            with _phase("train.read_loss", seconds):
                loss = float(loss)
            compute_s = time.perf_counter() - t0
            self.step += 1
            m = StepMetrics(
                self.step, loss, batch.data_wait_s, compute_s, batch.hits, batch.misses,
                prepare_s=seconds["train.prepare"], dispatch_s=seconds["train.dispatch"],
            )
            self.metrics.append(m)
            if self.step % self.tcfg.log_every == 0:
                print(
                    f"step {self.step} loss {loss:.4f} "
                    f"wait {m.data_wait_s*1e3:.1f}ms prepare {m.prepare_s*1e3:.1f}ms "
                    f"dispatch {m.dispatch_s*1e3:.1f}ms step {m.compute_s*1e3:.1f}ms "
                    f"miss {batch.misses}/{batch.hits + batch.misses}"
                )
            self._maybe_checkpoint()
        return True

    # -- paper metrics ------------------------------------------------------------
    def epoch_wait_summary(self) -> Dict[int, float]:
        out: Dict[int, float] = {}
        for s in self.loader.epoch_history:
            out[s.epoch] = s.data_wait_seconds
        return out


def elastic_repartition(loader: DeliLoader, new_rank: int, new_world: int) -> None:
    """Elastic scaling: re-partition the sample space when the data-parallel
    world changes (nodes joined/left).  The cache is preserved — entries are
    keyed by dataset index, so samples that stay on this node keep hitting;
    the prefetcher simply starts announcing the new partition."""
    from repro.core.sampler import DistributedPartitionSampler

    old = loader.sampler
    loader.sampler = DistributedPartitionSampler(
        n_samples=old.n_samples,
        rank=new_rank,
        world=new_world,
        seed=getattr(old, "seed", 0),
    )
    loader.sampler.set_epoch(loader.state_dict()["epoch"])
    loader._resume_cursor = 0  # partition changed: restart the epoch slice
