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
    """Under a profiler trace every step is one ``train.step`` span with its
    ``step_num``, holding one span of each host phase; the phase counters
    agree with what they time."""
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
    for _, a, b, _ in steps:
        inside = sorted((s for s in spans if s[0] in PHASES and a <= s[1] and s[2] <= b),
                        key=lambda s: s[1])
        assert [s[0] for s in inside] == list(PHASES)
    assert sum(s[0] in PHASES for s in spans) == 3 * len(PHASES)
    for m in t.metrics:
        assert m.prepare_s >= 0 and 0 <= m.dispatch_s <= m.compute_s


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
