"""Synthetic data pipeline: deterministic, shardable, resumable.

Batches are generated per-step from a counter-based RNG (seed ^ step), so the
pipeline is stateless — resuming from checkpoint step N reproduces the exact
stream with no saved iterator state, and every host generates only its own
shard (addressable-shard generation under a mesh). Modality frontends are
stubs per the assignment: audio/vision inputs are precomputed frame/patch
embeddings.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, Optional

import jax
import numpy as np

from repro.dist.sharding import batch_spec
from repro.models.config import ModelConfig


class SyntheticPipeline:
    def __init__(self, cfg: ModelConfig, batch: int, seq: int,
                 mesh: Optional[Any] = None, seed: int = 1234,
                 start_step: int = 0) -> None:
        self.cfg = cfg
        self.batch = batch
        self.seq = seq
        self.mesh = mesh
        self.seed = seed
        self.step = start_step

    # -- deterministic per-step generation ------------------------------------
    def _rng(self, step: int) -> np.random.Generator:
        return np.random.default_rng((self.seed << 20) ^ step)

    def host_batch(self, step: int) -> Dict[str, np.ndarray]:
        cfg = self.cfg
        rng = self._rng(step)
        out: Dict[str, np.ndarray] = {}
        if cfg.family in ("encdec", "audio"):
            out["frames"] = rng.standard_normal(
                (self.batch, self.seq, cfg.d_model), dtype=np.float32)
            dec_len = min(self.seq, 4096)
            out["tokens"] = rng.integers(
                0, cfg.vocab_size, (self.batch, dec_len), dtype=np.int32)
        elif cfg.family == "vlm":
            out["patches"] = rng.standard_normal(
                (self.batch, cfg.n_prefix_tokens, cfg.d_model), dtype=np.float32)
            out["tokens"] = rng.integers(
                0, cfg.vocab_size, (self.batch, self.seq - cfg.n_prefix_tokens),
                dtype=np.int32)
        else:
            out["tokens"] = rng.integers(
                0, cfg.vocab_size, (self.batch, self.seq), dtype=np.int32)
        return out

    def place(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """Put a host batch on the mesh (batch dim over the data axes);
        without a mesh the jitted step takes the host arrays as they are."""
        if self.mesh is None:
            return batch
        placed = {}
        for k, v in batch.items():
            trailing = (None,) * (v.ndim - 1)
            placed[k] = jax.device_put(v, batch_spec(self.mesh, *trailing))
        return placed

    # -- iterator protocol ------------------------------------------------------
    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return self

    def __next__(self) -> Dict[str, Any]:
        b = self.place(self.host_batch(self.step))
        self.step += 1
        return b

    # -- resumability -------------------------------------------------------------
    def state_dict(self) -> Dict[str, int]:
        return {"step": self.step, "seed": self.seed}

    def restore(self, state: Dict[str, int]) -> None:
        self.step = state["step"]
        self.seed = state["seed"]
