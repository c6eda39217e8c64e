"""Fault-tolerant training loop: MGit-versioned checkpoints, restart, stragglers.

The Trainer wires together the substrates: synthetic pipeline, jitted
train_step, CheckpointManager (every checkpoint is an MGit version node;
restart resumes from the latest committed one, including onto a different
mesh), and the straggler monitor. Given a mesh, the state is created
sharded per ``param_spec``, batches are placed per ``batch_spec``, and the
step is traced under ``use_mesh`` so the model's ``shard`` constraints hold.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data import SyntheticPipeline
from repro.dist.sharding import state_shardings, use_mesh
from repro.ft import ElasticRestart, StepTimer, StragglerPolicy
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.store.checkpoint import CheckpointManager
from repro.train.step import init_state, make_train_step


class Trainer:
    def __init__(self, cfg: ModelConfig, *, batch: int = 8, seq: int = 128,
                 opt_cfg: Optional[adamw.AdamWConfig] = None,
                 n_microbatches: int = 1, compress_grads: bool = False,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 50, mesh: Optional[Any] = None,
                 seed: int = 0,
                 on_metrics: Optional[Callable[[int, Dict], None]] = None,
                 commit_every: Optional[int] = None,
                 lossy_tier: bool = False, keyframe_every: int = 8):
        self.cfg = cfg
        self.mesh = mesh
        # ``commit_every`` is the continuous-checkpointing cadence knob
        # (DESIGN.md §15) — it overrides the legacy checkpoint_every name
        self.checkpoint_every = (commit_every if commit_every is not None
                                 else checkpoint_every)
        self.on_metrics = on_metrics
        self.pipeline = SyntheticPipeline(cfg, batch=batch, seq=seq, mesh=mesh,
                                          seed=seed)
        step_fn = make_train_step(cfg, opt_cfg, n_microbatches=n_microbatches,
                                  compress_grads=compress_grads)
        init = functools.partial(init_state, cfg, seed,
                                 compress_grads=compress_grads)
        if mesh is None:
            self.train_step = jax.jit(step_fn, donate_argnums=(0,))
            self.state = init()
        else:
            shardings = state_shardings(mesh, jax.eval_shape(init))
            self.train_step = jax.jit(
                step_fn, donate_argnums=(0,),
                out_shardings=(shardings, NamedSharding(mesh, P())))
            self.state = jax.jit(init, out_shardings=shardings)()
        self.timer = StepTimer()
        self.ckpt: Optional[CheckpointManager] = None
        self.start_step = 0
        if checkpoint_dir is not None:
            self.ckpt = CheckpointManager(
                checkpoint_dir, model_name=cfg.name,
                tier="lossy" if lossy_tier else "exact",
                keyframe_every=keyframe_every)
            latest = self.ckpt.latest_step()
            if latest is not None:  # crash restart: resume from last commit
                # the lossy tier may resolve to the nearest exact ancestor,
                # so resume from the step restore actually returned
                if mesh is None:
                    self.state, restored = self.ckpt.restore(
                        step=latest, template=self.state)
                else:
                    template = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                       sharding=x.sharding),
                        self.state)
                    self.state, restored = self.ckpt.restore_sharded(
                        template, step=latest)
                self.start_step = restored
                self.pipeline.step = restored
        # straggler escalation bottoms out in evict + elastic restart from
        # the last committed version (ft/straggler.py) when versioning is on
        self.elastic = ElasticRestart(self) if self.ckpt is not None else None
        self.policy = StragglerPolicy(evict_fn=self.elastic)

    def run(self, n_steps: int) -> Dict[str, list]:
        history: Dict[str, list] = {"loss": [], "step_time": []}
        for step in range(self.start_step, self.start_step + n_steps):
            batch = self.pipeline.place(self.pipeline.host_batch(step))
            t0 = time.perf_counter()
            with use_mesh(self.mesh):
                self.state, metrics = self.train_step(self.state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            history["loss"].append(loss)
            history["step_time"].append(dt)
            event = self.timer.record(step, dt)
            if event is not None:
                self.policy.on_event(event)
            if self.ckpt is not None and (step + 1) % self.checkpoint_every == 0:
                self.ckpt.save(step + 1, self.state)  # async, MGit-versioned
            if self.on_metrics is not None:
                self.on_metrics(step, {"loss": loss, "step_time": dt, **{
                    k: float(v) for k, v in metrics.items() if k != "loss"}})
        if self.ckpt is not None:
            self.ckpt.wait()
        return history
