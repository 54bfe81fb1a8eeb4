"""Training loop + fault tolerance: loss decreases, checkpoints are atomic,
kill/restart resumes exactly, elastic re-mesh restores, stragglers flagged,
gradient compression stays close to exact."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import get_reduced
from repro.data.pipeline import DataPipeline, SyntheticCorpus
from repro.distributed.fault_tolerance import StragglerMonitor, plan_elastic_restart
from repro.models.registry import build_model
from repro.training.optimizer import (
    AdamWConfig,
    adamw_update,
    compressed_psum,
    init_adamw,
    lr_at,
)
from repro.training.train_loop import Trainer


def _mk_trainer(tmp, steps_cfg=None, ckpt_every=5):
    cfg = get_reduced("libra-proxy-125m")
    model = build_model(cfg)
    corpus = SyntheticCorpus(cfg.vocab_size, seed=1)
    pipe = DataPipeline(corpus, batch=4, seq_len=32)
    opt = steps_cfg or AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=200)
    return Trainer(model, opt, pipe, checkpoint_dir=tmp,
                   checkpoint_every=ckpt_every, seed=0)


def test_loss_decreases(tmp_path):
    t = _mk_trainer(str(tmp_path))
    hist = t.train(30)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first - 0.5, (first, last)


def test_checkpoint_resume_exact(tmp_path):
    t1 = _mk_trainer(str(tmp_path / "a"), ckpt_every=10 ** 6)
    t1.train(10)
    t1.save(blocking=True)
    loss_continue = t1.train(5)[-1]["loss"]

    t2 = _mk_trainer(str(tmp_path / "a"), ckpt_every=10 ** 6)
    assert t2.resume()
    assert t2.step == 10
    loss_resumed = t2.train(5)[-1]["loss"]
    assert abs(loss_continue - loss_resumed) < 1e-5, \
        "restart must continue bit-exactly (params+opt+data state)"


def test_preemption_checkpoint(tmp_path):
    t = _mk_trainer(str(tmp_path))
    t.train(3)
    t._preempted = True  # simulated SIGTERM
    t.train(10)
    assert t.step == 3  # stopped immediately
    assert t.ckpt.latest_step() == 3  # final checkpoint written


def test_atomic_commit_survives_partial_save(tmp_path):
    t = _mk_trainer(str(tmp_path))
    t.train(6)
    t.save(blocking=True)
    # simulate a crash mid-save: stray .tmp dir must be ignored
    os.makedirs(str(tmp_path / "step_000000099.tmp"))
    t2 = _mk_trainer(str(tmp_path))
    assert t2.resume()
    assert t2.step in (5, 6)


def test_elastic_restore_other_mesh(tmp_path):
    """Restore a checkpoint onto a different mesh (elastic restart)."""
    t1 = _mk_trainer(str(tmp_path))
    t1.train(4)
    t1.save(blocking=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    t2 = _mk_trainer(str(tmp_path))
    assert t2.resume(mesh=mesh)
    # params usable on the new mesh
    h = t2.train(2)
    assert np.isfinite(h[-1]["loss"])


def test_elastic_plan():
    p = plan_elastic_restart(2, 1)
    assert p.mesh_shape == (16, 16) and p.global_batch_scale == 0.5


def test_straggler_monitor():
    mon = StragglerMonitor(n_slices=4, factor=1.5, patience=2)
    for step in range(12):
        for s in range(4):
            mon.record(s, 1.0 if s != 3 else 3.0)  # slice 3 is slow
        bad = mon.evaluate()
    assert bad == [3]


def test_wsd_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, schedule="wsd")
    lrs = [float(lr_at(cfg, jnp.int32(s))) for s in range(0, 101, 5)]
    assert lrs[0] < 0.2              # warmup
    assert abs(lrs[10] - 1.0) < 1e-6  # stable plateau
    assert lrs[-1] < 0.05            # decay tail


def test_gradient_compression_close_to_exact():
    """int8 compressed psum with error feedback: single-participant mean
    must track the exact gradient closely; residual carries the error."""
    mesh = jax.make_mesh((1,), ("pod",), axis_types=(AxisType.Auto,))
    g = {"w": jnp.array(np.random.default_rng(0).standard_normal((64, 64)),
                        jnp.float32)}
    err = jax.tree.map(jnp.zeros_like, g)

    def f(g, err):
        return compressed_psum(g, "pod", err)

    out, err2 = jax.shard_map(
        f, mesh=mesh, in_specs=(jax.sharding.PartitionSpec(),) * 2,
        out_specs=(jax.sharding.PartitionSpec(),) * 2, check_vma=False)(g, err)
    rel = float(jnp.linalg.norm(out["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    assert rel < 0.02, rel
    # error feedback: residual equals the quantisation error
    assert float(jnp.abs(err2["w"]).max()) > 0
