#!/usr/bin/env python3
"""Smoke run of MGit's main path on a TPU, through its normal entry points.

    python chip_smoke.py [--seed N]       # one chip: phases A, B and C
    python chip_smoke.py --four-chips     # the sharded path only (4 chips)

Model: qwen3-0.6b at its published widths and dtype (bf16), depth cut to
``N_LAYERS`` layers (one layer is a whole period of a dense model); weights
are random from ``--seed``.

A  train    ``Trainer`` with continuous checkpointing in the exact tier takes
            ``STEPS`` steps and commits a full and then a delta version; a
            second ``Trainer`` on the same directory resumes, its state must
            equal the committed state bit for bit, and it takes one more step.
B  lineage  A depth-``max_chain_depth`` chain of sparse float32 finetunes of
            those weights (the G2 version-chain statistics with G1's frozen
            fraction, ``benchmarks/pools.py``), stored one tensor per layer
            as published checkpoints are, is committed through
            ``LineageGraph``/``ArtifactStore``; the tip is checked out from a
            cold store and built into a ``ModelPool`` view with
            ``verify=True``. Every tensor must match its manifest truth hash.
C  kernels  Each storage kernel at a real-width leaf against its NumPy twin,
            bit for bit: lineages committed on the chip are checked out by
            CPU hosts (clone, hub, replica), so any difference is a failure.

``--four-chips`` runs only the sharded path: ``Trainer`` on a 2x2
("data", "model") mesh takes ``STEPS`` steps and commits;
``restore_sharded`` lays the checkpoint out on a 4x1 mesh and must equal the
saved state bit for bit; the losses must match the same steps on one device
within bf16 tolerance.

Each phase prints one JSON line (wall and compile seconds, bytes committed,
Pallas dispatches per kernel, mismatch counts, peak device bytes). The last
line is ``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit
code is 0 only when every phase passed and, on one chip, every storage
kernel ran as a Pallas kernel at least once. With no TPU the script exits 2
before any phase runs. Lineage repositories live in a temporary directory
that is deleted on exit.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
from typing import Any, Callable, Dict, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import pools  # noqa: E402
from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.common.hashing import tensor_hash  # noqa: E402
from repro.core import LineageGraph, ModelArtifact  # noqa: E402
from repro.dist.sharding import state_shardings  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.ref import fingerprint_host, quant_scale  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.serve import ModelPool  # noqa: E402
from repro.store import ArtifactStore  # noqa: E402
from repro.store.checkpoint import (CheckpointManager, flatten_state,  # noqa: E402
                                    state_graph)
from repro.store.delta import host_dequant, host_snapshot  # noqa: E402
from repro.train import Trainer  # noqa: E402

ARCH = "qwen3-0.6b"
N_LAYERS = 2
BATCH, SEQ = 8, 512
STEPS, COMMIT_EVERY = 4, 2     # commits at step 2 (full) and 4 (delta)
EPS = 1e-4                     # the store's default quantization bound
#: losses of the sharded and the one-device run may differ by bf16 rounding
#: of differently ordered reductions: 8 significand bits, 2^-7 ≈ 0.8%
LOSS_RTOL = 1e-2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------

_T0 = time.perf_counter()


def _log(msg: str) -> None:
    """Progress on stderr, so a run cut short still shows how far it got."""
    print(f"[chip_smoke {time.perf_counter() - _T0:8.1f}s] {msg}",
          file=sys.stderr, flush=True)


class CompileClock:
    """Sums XLA backend compile seconds while installed."""

    def __init__(self) -> None:
        self.seconds = 0.0

    def _on_event(self, event: str, seconds: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.seconds += seconds

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def _peak_bytes() -> Any:
    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats if s]
    return max(peaks) if peaks else None


def run_phase(name: str, clock: CompileClock, fn: Callable, *args
              ) -> Tuple[Any, Dict[str, Any]]:
    """Run one phase; print and return its JSON line (with its result)."""
    _log(f"phase {name}")
    t0, c0 = time.perf_counter(), clock.seconds
    d0 = ops.DISPATCHES.snapshot()
    result, line = fn(*args)
    d1 = ops.DISPATCHES.snapshot()
    line = {"phase": name, "ok": line.pop("ok"),
            "wall_s": time.perf_counter() - t0,
            "compile_s": clock.seconds - c0,
            **line,
            "pallas_dispatches": {k: int(d1[k] - d0.get(k, 0)) for k in d1},
            "peak_bytes_in_use": _peak_bytes()}
    print(json.dumps(line), flush=True)
    return result, line


def _bits(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x)).reshape(-1).view(np.uint8)


def _bit_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and np.array_equal(_bits(a), _bits(b)))


def _differing_leaves(a, b) -> int:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return max(len(la), len(lb))
    return sum(not _bit_equal(x, y) for x, y in zip(la, lb))


def _differing_elements(a, b) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return max(a.size, b.size)
    w = np.dtype(f"u{a.dtype.itemsize}")
    return int(np.count_nonzero(np.ascontiguousarray(a).view(w)
                                != np.ascontiguousarray(b).view(w)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train(cfg, workdir: str, seed: int, batch: int, seq: int):
    """A: train with continuous checkpointing, resume, one more step."""
    ckdir = os.path.join(workdir, "ckpt")
    os.makedirs(ckdir)
    t1 = Trainer(cfg, batch=batch, seq=seq, checkpoint_dir=ckdir,
                 commit_every=COMMIT_EVERY, seed=seed)
    losses = t1.run(STEPS)["loss"]
    _log(f"trained {STEPS} steps; waiting for commits")
    t1.ckpt.close()
    store, lineage = t1.ckpt.store, t1.ckpt.lineage
    depths = [store.get_manifest(
        lineage.nodes[f"{cfg.name}/step{s}"].artifact_ref)["depth"]
        for s in range(COMMIT_EVERY, STEPS + 1, COMMIT_EVERY)]
    committed = store.cas.physical_bytes()
    saved = jax.device_get(t1.state)
    del t1, store, lineage

    _log("committed; resuming")
    t2 = Trainer(cfg, batch=batch, seq=seq, checkpoint_dir=ckdir,
                 commit_every=COMMIT_EVERY, seed=seed)
    resumed_at = t2.start_step
    differing = _differing_leaves(saved, t2.state)
    resumed_losses = t2.run(1)["loss"]
    t2.ckpt.close()
    losses = losses + resumed_losses
    ok = (depths[0] == 0 and all(d >= 1 for d in depths[1:])
          and resumed_at == STEPS and differing == 0
          and bool(np.isfinite(losses).all()))
    return saved["params"], {
        "ok": ok, "losses": losses, "commit_depths": depths,
        "resumed_at_step": resumed_at, "bytes_committed": committed,
        "mismatches": {"restored_leaves": differing}}


def _per_layer(params) -> Dict[str, np.ndarray]:
    """Float32 weights, one tensor per layer as published checkpoints
    store them (the model stacks its layers on a leading axis)."""
    flat = {k: np.asarray(v, np.float32)
            for k, v in flatten_state(params).items()}
    out = {k: v for k, v in flat.items() if not k.startswith("layers/")}
    stacked = {k[len("layers/"):]: v for k, v in flat.items()
               if k.startswith("layers/")}
    for i in range(len(next(iter(stacked.values())))):
        for name, value in stacked.items():
            out[f"layers/{i}/{name}"] = value[i].copy()
    return out


def phase_lineage(params, workdir: str, seed: int):
    """B: commit a depth-max_chain_depth chain of sparse finetunes, check
    out its tip cold, build its pool view with verification."""
    root = os.path.join(workdir, "lineage")
    store = ArtifactStore(root=root)
    depth = store.max_chain_depth
    weights = _per_layer(params)
    model = ModelArtifact(state_graph(weights, ARCH), weights,
                          model_type=ARCH)
    graph = LineageGraph(path=root, store=store)
    graph.add_node(model, "base")
    _log("committed the base")
    names = ["base"]
    for k in range(1, depth + 1):
        # G2 version-chain update statistics; G1's frozen fraction keeps
        # the first 30% of the leaves in key order, the embedding first
        model = pools.finetune(model, seed=seed * 1000 + k, scale=5e-5,
                               density=0.1, freeze_frac=0.3)
        name = f"ft{k}"
        graph.add_node(None, name, model_type=ARCH)
        graph.add_version_edge(names[-1], name)
        graph.add_node(model, name)
        names.append(name)
        _log(f"committed hop {k}")
    refs = [graph.nodes[n].artifact_ref for n in names]
    depths = [store.get_manifest(r)["depth"] for r in refs]
    committed = store.cas.physical_bytes()
    tip = refs[-1]

    cold = ArtifactStore(root=root)  # a fresh reader: no warm caches
    _log("checking out the tip")
    manifest = cold.get_manifest(tip)
    checkout = cold.materialize_artifact(tip).params
    hash_mismatches = sum(tensor_hash(checkout[k]) != e["hash"]
                          for k, e in manifest["params"].items())
    _log("building the pool view")
    pool = ModelPool(cold, verify=True)
    view = pool.get(tip)  # raises BitIdentityError on any divergence
    pool_mismatches = sum(not _bit_equal(view.params[k], checkout[k])
                          for k in manifest["params"])
    stats = pool.stats()
    ok = (depths == list(range(depth + 1)) and hash_mismatches == 0
          and pool_mismatches == 0
          and stats["params_verified"] + stats["params_aliased"]
          == len(manifest["params"]))
    kinds: Dict[str, int] = {}
    for e in manifest["params"].values():
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
    return weights, {
        "ok": ok, "chain_depths": depths, "tip_entry_kinds": kinds,
        "bytes_committed": committed,
        "pool": {k: stats[k] for k in ("params_verified", "params_aliased",
                                       "fused_applies", "segments_applied")},
        "mismatches": {"checkout_vs_truth_hash": hash_mismatches,
                       "pool_vs_checkout": pool_mismatches}}


def phase_kernels(leaf: np.ndarray, seed: int):
    """C: every storage kernel at one real-width leaf vs its NumPy twin."""
    rng = np.random.default_rng(seed)
    step = quant_scale(EPS)
    # spread the magnitudes so every exponent range is exercised
    p1 = (leaf * 10.0 ** rng.uniform(-2, 2, leaf.shape)).astype(np.float32)
    p2 = (p1 - rng.normal(scale=10 * step, size=leaf.shape)
          ).astype(np.float32)                      # |q| << 127: narrow
    wide = (p1 - rng.normal(scale=1000 * step, size=leaf.shape)
            ).astype(np.float32)                    # overflows int8
    bf16 = p1.astype(jax.numpy.bfloat16)
    qs = [rng.integers(-127, 128, leaf.shape).astype(np.int8)
          for _ in range(8)]
    qsum = np.sum(np.stack(qs).astype(np.int32), axis=0, dtype=np.int32)
    mism: Dict[str, int] = {}

    q, nz, fp, narrow = ops.snapshot_fused(p1, p2, eps=EPS)
    hq, hnz, hnarrow = host_snapshot(p1, p2, EPS)
    mism["snapshot_fused.q"] = _differing_elements(q, hq)
    mism["snapshot_fused.zeros"] = int(nz != hnz) + int(narrow != hnarrow)
    mism["snapshot_fused.fingerprint"] = int(
        fp != ops.fold_fingerprint(p2, fingerprint_host(p2)))

    q, nz, _, narrow = ops.snapshot_fused(p1, wide, eps=EPS,
                                          with_fingerprint=False)
    hq, hnz, hnarrow = host_snapshot(p1, wide, EPS)
    mism["delta_quantize.q"] = _differing_elements(q, hq)
    mism["delta_quantize.zeros"] = int(nz != hnz) + int(narrow != hnarrow)
    wide_q = hq

    mism["dequant_apply.int8"] = _differing_elements(
        ops.dequant_apply(p1, qs[0], eps=EPS), host_dequant(p1, qs[0], EPS))
    mism["dequant_apply.int32"] = _differing_elements(
        ops.dequant_apply(p1, wide_q, eps=EPS), host_dequant(p1, wide_q, EPS))
    mism["dequant_apply.bf16"] = _differing_elements(
        ops.dequant_apply(bf16, qs[0], eps=EPS),
        host_dequant(bf16, qs[0], EPS, out_dtype=bf16.dtype))
    mism["chain_apply"] = _differing_elements(
        ops.chain_apply(p1, qs, eps=EPS, out_dtype="float32"),
        host_dequant(p1, qsum, EPS))
    for x in (p1, bf16):
        mism[f"fingerprint.{x.dtype}"] = int(
            ops.fingerprint(x) != ops.fold_fingerprint(x, fingerprint_host(x)))
    return None, {"ok": not any(mism.values()), "leaf_shape": list(leaf.shape),
                  "mismatches": mism}


def phase_sharded(cfg, workdir: str, seed: int, batch: int, seq: int,
                  devices):
    """Four chips: sharded train -> commit -> restore_sharded on another
    layout, compared with the same steps on one device."""
    ckdir = os.path.join(workdir, "ckpt-sharded")
    os.makedirs(ckdir)
    grid = np.asarray(devices[:4])
    mesh = jax.sharding.Mesh(grid.reshape(2, 2), ("data", "model"))
    tr = Trainer(cfg, batch=batch, seq=seq, checkpoint_dir=ckdir,
                 commit_every=STEPS, seed=seed, mesh=mesh)
    losses = tr.run(STEPS)["loss"]
    tr.ckpt.close()
    committed = tr.ckpt.store.cas.physical_bytes()
    saved = jax.device_get(tr.state)

    other = jax.sharding.Mesh(grid.reshape(4, 1), ("data", "model"))
    shardings = state_shardings(other, tr.state)
    template = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tr.state, shardings)
    del tr
    restored, step = CheckpointManager(ckdir, model_name=cfg.name
                                       ).restore_sharded(template)
    misplaced = sum(
        not r.sharding.is_equivalent_to(s, r.ndim) for r, s in zip(
            jax.tree_util.tree_leaves(restored),
            jax.tree_util.tree_leaves(shardings)))
    differing = _differing_leaves(saved, restored)
    del restored
    _log("restored on 4x1; running the same steps on one device")

    single = Trainer(cfg, batch=batch, seq=seq, seed=seed)
    ref_losses = single.run(STEPS)["loss"]
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    ok = (step == STEPS and differing == 0 and misplaced == 0
          and bool(np.isfinite(losses).all()) and max(rel) <= LOSS_RTOL)
    return None, {
        "ok": ok, "losses": losses, "one_device_losses": ref_losses,
        "max_loss_rel_diff": max(rel), "restored_step": step,
        "bytes_committed": committed,
        "mismatches": {"restored_leaves": differing,
                       "restored_placement": misplaced}}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, data and finetunes")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path, on four chips")
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cfg = dataclasses.replace(get_config(ARCH), n_layers=N_LAYERS)
    lines = []
    with CompileClock() as clock, \
            tempfile.TemporaryDirectory(prefix="mgit-chip-smoke-") as work:
        if args.four_chips:
            lines.append(run_phase("sharded", clock, phase_sharded, cfg,
                                   work, args.seed, BATCH, SEQ, devices)[1])
        else:
            params, line = run_phase("train", clock, phase_train, cfg, work,
                                     args.seed, BATCH, SEQ)
            lines.append(line)
            weights, line = run_phase("lineage", clock, phase_lineage,
                                      params, work, args.seed)
            lines.append(line)
            lines.append(run_phase("kernels", clock, phase_kernels,
                                   weights["layers/0/mlp/w_in"],
                                   args.seed)[1])
    ok = all(line["ok"] for line in lines)
    if not args.four_chips:  # no storage kernel may have given way to ref
        idle = [k for k, v in ops.DISPATCHES.snapshot().items() if not v]
        if idle:
            _log(f"never dispatched: {idle}")
            ok = False
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
