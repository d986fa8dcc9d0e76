"""Integration: Trainer over the real threaded DELI pipeline — loss falls,
checkpoint/restore resumes exactly, elastic re-partitioning works."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy; excluded from the smoke lane

from repro.core import PrefetchConfig, RealClock
from repro.data import decode_tokens, make_lm_spec
from repro.models.config import ArchConfig
from repro.training import checkpoint as ckpt
from repro.training.loop import Trainer, TrainerConfig, elastic_repartition
from repro.training.optimizer import OptSettings

SEQ, CACHE, BATCH = 64, 128, 4
CFG = ArchConfig(
    name="lm-test", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=512, dtype="float32", attn_chunk=64,
)


def _trainer(ckpt_dir=None, every=5, n_samples=512, cfg=CFG):
    # ISSUE 4 satellite: the trainer's pipeline comes from the declarative
    # LM spec (make_lm_pipeline folded into DataPlaneSpec).
    spec = make_lm_spec(
        n_samples=n_samples, seq_len=SEQ, vocab=CFG.vocab, batch_size=BATCH,
        cache_items=CACHE, policy=PrefetchConfig.fifty_fifty(CACHE),
    )
    cluster = spec.build_runtime(clock=RealClock())
    loader, service = cluster.loaders[0], cluster.services[0]
    t = Trainer(
        cfg, loader,
        TrainerConfig(seq_len=SEQ, batch_size=BATCH, checkpoint_dir=ckpt_dir,
                      checkpoint_every=every, log_every=1000),
        decode_fn=decode_tokens,
        settings=OptSettings(lr=3e-3, moment_dtype="float32"),
    )
    return t, service


def test_loss_decreases_through_deli_pipeline():
    t, svc = _trainer()
    with svc:
        metrics = t.train(30)
    assert len(metrics) == 30
    first = np.mean([m.loss for m in metrics[:5]])
    last = np.mean([m.loss for m in metrics[-5:]])
    assert last < first, (first, last)
    assert all(np.isfinite(m.loss) for m in metrics)


def test_checkpoint_restore_resumes_exactly():
    d = tempfile.mkdtemp()
    t1, svc1 = _trainer(ckpt_dir=d, every=5)
    with svc1:
        t1.train(12)
    assert ckpt.latest_step(d) == 10

    t2, svc2 = _trainer(ckpt_dir=d, every=5)
    assert t2.try_restore()
    assert t2.step == 10
    # params match the checkpointed run bit-exactly
    p1 = jax.tree.leaves(
        ckpt.restore_checkpoint(d, 10)[0]
    )
    p2 = jax.tree.leaves(t2.params)
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(np.asarray(a, np.float32).ravel(),
                                      np.asarray(b, np.float32).ravel())
    with svc2:
        t2.train(3)
    assert t2.step == 13


def test_bf16_checkpoint_resumes_exactly():
    """bf16 leaves survive np.savez (stored as raw '|V2' records) bit for
    bit, and the resumed run's next step equals the uninterrupted one's."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    d = tempfile.mkdtemp()
    t1, svc1 = _trainer(ckpt_dir=d, every=5, cfg=cfg)
    with svc1:
        t1.train(10)
    saved = [np.asarray(x) for x in jax.tree.leaves((t1.params, t1.opt_state))]
    assert saved[0].dtype == jnp.bfloat16
    with svc1:
        t1.train(1)

    t2, svc2 = _trainer(ckpt_dir=d, every=5, cfg=cfg)
    assert t2.try_restore() and t2.step == 10
    restored = [np.asarray(x) for x in jax.tree.leaves((t2.params, t2.opt_state))]
    for a, b in zip(saved, restored):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    with svc2:
        t2.train(1)
    assert t2.metrics[-1].loss == t1.metrics[-1].loss


def test_checkpoint_atomic_and_gc():
    d = tempfile.mkdtemp()
    t, svc = _trainer(ckpt_dir=d, every=2)
    t.tcfg = TrainerConfig(seq_len=SEQ, batch_size=BATCH, checkpoint_dir=d,
                           checkpoint_every=2, keep_checkpoints=2, log_every=1000)
    t._ckpt.keep = 2  # the AsyncCheckpointer captured keep at __init__
    with svc:
        t.train(10)
    steps = ckpt.list_steps(d)
    assert len(steps) <= 2 and steps[-1] == 10  # gc keeps the latest


def test_elastic_repartition_halves_partition():
    t, svc = _trainer(n_samples=512)
    with svc:
        t.train(3)
    assert len(t.loader.sampler) == 512
    elastic_repartition(t.loader, new_rank=1, new_world=2)
    assert len(t.loader.sampler) == 256
    assert t.loader.sampler.rank == 1
    with svc:
        t.train(3)  # keeps training on the new partition
    assert t.step == 6


PHASES = ("train.next_batch", "train.prepare", "train.dispatch", "train.block", "train.read_loss")


def test_each_step_and_its_phases_are_profiler_spans(tmp_path):
    """Under a profiler trace each pass of the loop is one ``train.step`` span
    with the ``step_num`` of the step it requests, and every phase span lies
    in one.  Each step has one span of each phase, tagged with its
    ``step_num``; step n is waited for and read only after step n+1 is
    dispatched.  Each phase counter spans its phase's span and no more than
    the host time between the spans on either side of it."""
    from jax.profiler import ProfileData

    t, svc = _trainer()
    with svc:
        t.train(1)  # compiles outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            t.train(3)
        finally:
            jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = [
        (e.name, e.start_ns, e.end_ns, dict(e.stats))
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("train.")
    ]
    steps = sorted((s for s in spans if s[0] == "train.step"), key=lambda s: s[1])
    assert [s[3].get("step_num") for s in steps] == [2, 3, 4]
    phases = sorted((s for s in spans if s[0] in PHASES), key=lambda s: s[1])
    assert len(phases) == 3 * len(PHASES)
    for _, a, b, _ in phases:
        assert any(sa <= a and b <= sb for _, sa, sb, _ in steps)
    of = {(s[0], s[3].get("step_num")): s for s in phases}
    assert set(of) == {(p, n) for p in PHASES for n in (2, 3, 4)}
    for n in (2, 3):
        dispatched_next = of["train.dispatch", n + 1][2]
        assert of["train.block", n][1] >= dispatched_next
        assert of["train.read_loss", n][1] >= dispatched_next

    def bounds(name, n):
        """The span's host seconds, and those between its neighbours."""
        i = phases.index(of[name, n])
        lo = phases[i - 1][2] if i else steps[0][1]
        hi = phases[i + 1][1] if i + 1 < len(phases) else steps[-1][2]
        return (phases[i][2] - phases[i][1]) * 1e-9, (hi - lo) * 1e-9

    def agree(counter, *parts):
        inner = sum(bounds(name, n)[0] for name, n in parts)
        outer = sum(bounds(name, n)[1] for name, n in parts)
        # the span's clock and the counter's may differ by their rates
        assert inner * (1 - 1e-3) - 1e-6 <= counter <= outer * (1 + 1e-3) + 1e-6

    for m in t.metrics[1:]:
        n = m.step
        agree(m.prepare_s, ("train.prepare", n))
        agree(m.dispatch_s, ("train.dispatch", n))
        agree(m.compute_s, ("train.dispatch", n), ("train.block", n), ("train.read_loss", n))
    for m in t.metrics:
        assert m.prepare_s >= 0 and 0 <= m.dispatch_s <= m.compute_s


class _Watched:
    """The trainer's loader, recording at each batch request how many steps
    the trainer has read and a host copy of its parameters and moments, and
    every batch it hands over."""

    def __init__(self, trainer):
        self._loader, self._trainer = trainer.loader, trainer
        self.read_at_request, self.state_at_request, self.batches = [], [], []
        trainer.loader = self

    def __getattr__(self, name):
        return getattr(self._loader, name)

    def __iter__(self):
        batches = iter(self._loader)
        while True:
            t = self._trainer
            self.read_at_request.append(len(t.metrics))
            self.state_at_request.append([np.array(x) for x in jax.tree.leaves((t.params, t.opt_state))])
            batch = next(batches, None)
            if batch is None:
                return
            self.batches.append(batch)
            yield batch


def test_the_next_batch_is_requested_before_the_step_is_read():
    """Batch n+1 is requested while step n is in flight: at request k >= 2
    the trainer has read k-2 steps.  ``train(k)`` requests k batches and no
    more, so the loader's cursor is where k steps leave it."""
    t, svc = _trainer()
    watched = _Watched(t)
    with svc:
        t.train(6)
        assert watched.read_at_request == [0, 0, 1, 2, 3, 4]
        assert t.loader.state_dict()["cursor"] == 6 * BATCH
        t.train(2)
    assert watched.read_at_request == [0, 0, 1, 2, 3, 4, 6, 6]
    assert len(watched.batches) == 8 and t.step == 8
    assert t.loader.state_dict()["cursor"] == 8 * BATCH


def test_overlapped_steps_equal_the_serial_steps():
    """Six steps run one ahead give the losses, the final state and, at each
    batch request, the state after the steps before it, bit for bit, of the
    same jitted step applied in sequence to the same batches."""
    t, svc = _trainer()
    state = jax.tree.map(np.array, (t.params, t.opt_state))
    watched = _Watched(t)
    with svc:
        t.train(6)
    params, opt = jax.tree.map(jnp.asarray, state)
    losses = []
    for held, batch in zip(watched.state_at_request, watched.batches):
        serial = [np.asarray(x) for x in jax.tree.leaves((params, opt))]
        assert [a.tobytes() for a in held] == [b.tobytes() for b in serial]
        (loss, _), params, opt = t._step_fn(params, opt, t._to_device_batch(batch))
        losses.append(float(loss))
    assert [m.loss for m in t.metrics] == losses
    for a, b in zip(jax.tree.leaves((t.params, t.opt_state)), jax.tree.leaves((params, opt))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_the_loop_drains_before_a_checkpoint_and_at_the_last_step():
    """``overlapped`` is False where the loop read the step before requesting
    the next batch: a checkpoint step and the call's last step.  It is True
    elsewhere, also at an epoch's last step, whose next request (the one
    that finds the epoch's end) went out while it ran."""
    d = tempfile.mkdtemp()
    t, svc = _trainer(ckpt_dir=d, every=5, n_samples=64)  # 16 batches an epoch
    with svc:
        metrics = t.train(18, epochs=2)
    assert [m.step for m in metrics] == list(range(1, 19))
    assert [m.step for m in metrics if not m.overlapped] == [5, 10, 15, 18]
    assert ckpt.list_steps(d)[-1] == 15
    for m in metrics:
        assert m.prepare_s >= 0 and 0 <= m.dispatch_s <= m.compute_s


class _FailsAtFourthRequest(_Watched):
    def __iter__(self):
        for k, batch in enumerate(super().__iter__(), 1):
            if k == 4:
                raise OSError("the bucket is unreachable")
            yield batch


def test_an_exception_drains_the_step_in_flight():
    """An exception that leaves the loop leaves it after the step in flight
    is read: the loader failing at request 4 leaves three steps read, the
    third not overlapped, and the state after them alive."""
    t, svc = _trainer()
    _FailsAtFourthRequest(t)
    with svc, pytest.raises(OSError):
        t.train(6)
    assert t.step == 3
    assert [m.overlapped for m in t.metrics] == [True, True, False]
    assert not any(x.is_deleted() for x in jax.tree.leaves((t.params, t.opt_state)))


def test_epoch_end_stops_the_call_as_before():
    """The loader's epoch end ends a step loop without a step; the call
    goes on into the next epoch until ``epochs`` is reached."""
    t, svc = _trainer(n_samples=64)  # 16 batches an epoch
    with svc:
        metrics = t.train(100, epochs=2)
    assert len(metrics) == 32 and t.step == 32
    assert [m.step for m in metrics] == list(range(1, 33))


def test_the_step_takes_the_state_over_and_reads_counters():
    """The jitted step is handed the parameters and optimizer state
    (donated): after a step the old arrays are gone, the new ones live.  A
    model without MoE layers has no step counters; a dropless MoE model
    reads its held-expert rows with the loss."""
    t, svc = _trainer()
    old = jax.tree.leaves((t.params, t.opt_state))
    with svc:
        t.train(1)
    assert all(x.is_deleted() for x in old)
    assert not any(x.is_deleted() for x in jax.tree.leaves((t.params, t.opt_state)))
    assert t.metrics[-1].counters == {}

    moe_cfg = dataclasses.replace(
        CFG, family="moe", mlp_pattern=("moe",), n_experts=4, top_k=2, capacity_factor=0.0,
        router="sigmoid_bias", experts_held=2, mlp_act="relu2", d_ff=32,
    )
    t, svc = _trainer(cfg=moe_cfg)
    with svc:
        t.train(2)
    tokens = BATCH * SEQ
    for m in t.metrics:
        assert set(m.counters) == {"moe_rows", "moe_rows_max", "moe_experts"}
        # 2 layers, each routing 2 of 4 experts a token over 2 held experts
        assert 0 < m.counters["moe_rows"] <= 2 * 2 * tokens
        assert m.counters["moe_rows"] / 4 <= m.counters["moe_rows_max"] <= m.counters["moe_rows"]
        assert 1 <= m.counters["moe_experts"] <= 4


def test_checkpoint_copies_the_state_before_the_next_step_takes_it():
    """``AsyncCheckpointer.save`` holds its own host copy: the next step may
    be handed (and write over) the device buffers while the write runs."""
    d = tempfile.mkdtemp()
    t, svc = _trainer(ckpt_dir=d, every=1000)
    with svc:
        t.train(2)
    before = [np.array(x) for x in jax.tree.leaves((t.params, t.opt_state))]
    donated = jax.tree.leaves((t.params, t.opt_state))
    t._ckpt.save(t.step, t.params, t.opt_state)
    with svc:
        t.train(1)  # runs while the write may still be going
    t._ckpt.wait()
    assert all(x.is_deleted() for x in donated)
    params, opt, _, _ = ckpt.restore_checkpoint(d, 2, like=(t.params, t.opt_state))
    restored = [np.asarray(x) for x in jax.tree.leaves((params, opt))]
    assert len(restored) == len(before)
    for a, b in zip(before, restored):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
