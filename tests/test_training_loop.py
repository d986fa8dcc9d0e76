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
