"""Fault-tolerant Trainer: checkpoint/restart continuity + straggler hook."""

import dataclasses

import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.train import Trainer

CFG = ModelConfig(name="trainer-toy", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                  head_dim=16, dtype="float32", attn_chunk=16, remat="none")


def test_trainer_checkpoint_restart_continuity(tmp_path):
    # run 1: train 6 steps, checkpoint every 3
    t1 = Trainer(CFG, batch=4, seq=16, checkpoint_dir=str(tmp_path),
                 checkpoint_every=3, seed=3)
    h1 = t1.run(6)
    t1.ckpt.wait()

    # "crash" + restart: a fresh Trainer over the same dir resumes at step 6
    t2 = Trainer(CFG, batch=4, seq=16, checkpoint_dir=str(tmp_path),
                 checkpoint_every=3, seed=3)
    assert t2.start_step == 6
    # restored params match within the delta-quantization bound: MGit
    # checkpoints are LOSSY by design (paper §4, eps=1e-4, accuracy-gated);
    # the reconstructed tensors are persisted as the version's truth, so the
    # error is bounded per chain link, not compounding per save
    import jax
    bound = 3 * 2 * np.log1p(1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(t1.state["params"]),
                    jax.tree_util.tree_leaves(t2.state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=bound)
    # training continues from the same data position (deterministic pipeline)
    assert t2.pipeline.step == 6
    h2 = t2.run(2)
    assert len(h2["loss"]) == 2 and np.isfinite(h2["loss"]).all()


def test_trainer_checkpoints_are_versioned_and_compressed(tmp_path):
    t = Trainer(CFG, batch=4, seq=16, checkpoint_dir=str(tmp_path),
                checkpoint_every=2, seed=0)
    t.run(4)
    t.ckpt.wait()
    lineage = t.ckpt.lineage
    names = [n for n in lineage.nodes if n.startswith("trainer-toy/step")]
    assert len(names) == 2
    # consecutive checkpoints are linked by version edges
    first = f"trainer-toy/step2"
    assert lineage.nodes[first].version_children == ["trainer-toy/step4"]


def test_trainer_straggler_hook():
    t = Trainer(CFG, batch=2, seq=16)
    # feed synthetic timings through the same timer the loop uses
    for i in range(8):
        t.timer.record(i, 0.05)
    ev = t.timer.record(9, 0.5)
    assert ev is not None
    assert t.policy.on_event(ev) in ("log", "rebalance", "evict")


def test_trainer_on_a_mesh_resumes_through_restore_sharded(tmp_path):
    """Given a mesh, the state is laid out by ``param_spec`` and a restart
    resumes through ``restore_sharded`` onto that layout, bit for bit."""
    import jax
    from repro.dist.sharding import state_shardings
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    t1 = Trainer(CFG, batch=4, seq=16, checkpoint_dir=str(tmp_path),
                 commit_every=2, seed=3, mesh=mesh)
    t1.run(4)
    saved = jax.device_get(t1.state)
    t2 = Trainer(CFG, batch=4, seq=16, checkpoint_dir=str(tmp_path),
                 commit_every=2, seed=3, mesh=mesh)
    assert t2.start_step == 4
    leaves = jax.tree_util.tree_leaves
    for a, b, s in zip(leaves(saved), leaves(t2.state),
                       leaves(state_shardings(mesh, t2.state))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert b.sharding.is_equivalent_to(s, b.ndim)
    assert np.isfinite(t2.run(1)["loss"]).all()
