"""Training traffic: ``Trainer`` with continuous checkpointing.

The mix file sets the run: ``commit_every`` (save cadence in steps),
``tier``, ``optimizer`` (AdamW settings, handed to the program and to the
reference alike), ``reference_steps`` and ``reference_rows`` (sequences per
block of the reference), and ``limits`` for the compared numbers; the
configuration sets the batch (``train_batch``) and ``seq_len``.

Set-up builds one ``Trainer`` on a fresh checkpoint directory, puts in a
state made on the device from ``--seed``, and drives it through its first
three steps (kept for the reference) with the same ``run`` call the window
uses, then saves that state through its ``CheckpointManager``: the full
first version lands in set-up.
The window is one ``run`` that stops at the first save step after
``--seconds`` have passed, then waits until that save is durable.
``train_tokens_per_s`` is every token of the window's steps over that whole
time, save stalls and the final drain included.

``correct`` needs: the last save, restored by a fresh
``CheckpointManager``, equal to the live state bit for bit; and the first
three steps next to the configuration's plain reference (its ``reference``,
``chipbench/reference/dense_lm.py`` for the dense models):
each step's loss, the first clipped gradient (the optimizer's first moment
after one step over ``1 - b1``) and the parameters' change after three
steps, the last two by the worst leaf: the gap between the two norms over
the larger of the reference leaf's norm and the median leaf's.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import os
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import generate, trace
from chipbench.harness import dir_bytes, log


class _Stop(Exception):
    """Raised from the step hook to end the window on a save step."""


def batch_tokens(seed: int, step: int, batch: int, seq: int,
                 vocab: int) -> np.ndarray:
    """The tokens the program's synthetic pipeline feeds at ``step`` (the
    same draw, kept here so the reference needs nothing of the program)."""
    rng = np.random.default_rng((seed << 20) ^ step)
    return rng.integers(0, vocab, (batch, seq), dtype=np.int32)


def _flat(tree) -> Dict[str, Any]:
    return {jax.tree_util.keystr(p, simple=True, separator="/"): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@functools.partial(jax.jit, static_argnums=(0,))
def _init(structs, key):
    """Parameters of the program's layout, made from the seed: normal over
    sqrt(fan-in) for matrices, zeros for the norms (their scale is 1 + w)."""
    out = []
    for i, (path, shape, dt) in enumerate(structs):
        if path.rsplit("/", 1)[-1] in ("ln1", "ln2", "final_norm"):
            out.append(jnp.zeros(shape, dt))
            continue
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out.append((x / np.float32(np.sqrt(shape[-2]))).astype(dt))
    return out


def initial_params(template, seed: int):
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    structs = tuple((jax.tree_util.keystr(p, simple=True, separator="/"),
                     tuple(x.shape), str(x.dtype)) for p, x in paths)
    return jax.tree_util.tree_unflatten(
        treedef, _init(structs, generate.root_key(seed)))


def _same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return (a.size == b.size and a.dtype == b.dtype and np.array_equal(
        np.ascontiguousarray(a).reshape(-1).view(np.uint8),
        np.ascontiguousarray(b).reshape(-1).view(np.uint8)))


def gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
         skip=()) -> float:
    """Worst leaf: |norm(prog) - norm(ref)| over the larger of norm(ref) and
    the median leaf's norm(ref)."""
    keys = [k for k in ref if k not in skip]
    rn = {k: float(np.linalg.norm(np.asarray(ref[k], np.float64)))
          for k in keys}
    med = float(np.median(list(rn.values())))
    worst = 0.0
    for k in keys:
        pn = float(np.linalg.norm(np.asarray(prog[k], np.float64)))
        worst = max(worst, abs(pn - rn[k]) / max(rn[k], med, 1e-30))
    return worst


def readings(losses, grad, params3, ref, params0, b1: float) -> Dict:
    """The three compared numbers of one run against reference ``ref`` =
    ``(losses, clipped first gradient, params after three steps)``."""
    r_losses, r_grad, r_params3 = ref
    r_grad = {k: np.asarray(v) for k, v in _flat(r_grad).items()}
    gn = {k: float(np.linalg.norm(v)) for k, v in r_grad.items()}
    med = float(np.median(list(gn.values())))
    # leaves the reference does not move but by rounding: under Adam their
    # change is noise, so they are left out of the change by this rule
    still = {k for k, v in gn.items() if v < 1e-3 * med}
    p0 = {k: np.asarray(v, np.float64) for k, v in _flat(params0).items()}
    d_prog = {k: np.asarray(v, np.float64) - p0[k]
              for k, v in _flat(params3).items()}
    d_ref = {k: np.asarray(v, np.float64) - p0[k]
             for k, v in _flat(r_params3).items()}
    grad = {k: np.asarray(v, np.float64) / (1 - b1)
            for k, v in _flat(grad).items()}
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses, r_losses)),
            "grad_gap": gaps(grad, r_grad),
            "change_gap": gaps(d_prog, d_ref, skip=still),
            "still_leaves": len(still)}


def reference_model(cell):
    """The configuration's plain reference: ``chipbench/reference/<name>.py``
    named by its ``reference`` key."""
    return importlib.import_module("chipbench.reference."
                                   + cell.config.get("reference", "dense_lm"))


def program_config(cell):
    from repro.models.config import get_config
    return dataclasses.replace(get_config(cell.config["arch"]),
                               **cell.config["model"])


def reference_inputs(cell, seed: int, template):
    """Initial parameters (made from the seed again) and the first batches."""
    cfg = cell.config
    n = int(cell.mix["reference_steps"])
    batches = [batch_tokens(seed, s, cfg["train_batch"], cfg["seq_len"],
                            cfg["model"]["vocab_size"]) for s in range(n)]
    return initial_params(template, seed), batches


def run(run) -> Dict[str, Any]:
    from repro.optim import adamw
    from repro.store.checkpoint import CKPT_STATS, CheckpointManager
    from repro.train import Trainer
    cell, mix = run.cell, run.cell.mix
    cfg = program_config(cell)
    precision = cell.config.get("matmul_precision")
    if precision:
        jax.config.update("jax_default_matmul_precision", precision)
    batch, seq = cell.config["train_batch"], cell.config["seq_len"]
    every = int(mix["commit_every"])
    ckdir = os.path.join(run.work, "ckpt")
    os.makedirs(ckdir)
    steps: List[int] = []
    state = {"w0": None}

    def hook(step, metrics):
        steps.append(step)
        if (state["w0"] is not None and (step + 1) % every == 0
                and time.perf_counter() - state["w0"] >= run.seconds):
            raise _Stop

    tr = Trainer(cfg, batch=batch, seq=seq, checkpoint_dir=ckdir,
                 commit_every=every, seed=run.seed,
                 opt_cfg=adamw.AdamWConfig(**mix["optimizer"]),
                 lossy_tier=mix["tier"] == "lossy", on_metrics=hook)
    template = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tr.state["params"])
    tr.state = None
    params = initial_params(template, run.seed)
    tr.state = {"params": params, "opt": adamw.init(params),
                "step": jnp.zeros((), jnp.int32)}
    del params

    def advance(n: int) -> List[float]:
        losses = tr.run(n)["loss"]
        tr.start_step += n       # ``run`` starts at ``start_step`` each call
        return losses

    losses = advance(1)
    mu1 = jax.tree_util.tree_map(jnp.copy, tr.state["opt"].mu)
    losses += advance(int(mix["reference_steps"]) - 1)
    params3 = jax.tree_util.tree_map(jnp.copy, tr.state["params"])
    # the first (full) version lands in set-up, so the window's saves are
    # step deltas; it also compiles every leaf's fingerprint
    tr.ckpt.save(tr.start_step, tr.state)
    tr.ckpt.wait()
    log(f"set-up: {tr.start_step} steps, losses {losses}")
    setup_end = time.perf_counter()

    c0 = CKPT_STATS.snapshot()
    phys0 = dir_bytes(ckdir)
    compiles0 = run.clock.count
    first = len(steps)
    with run.window() as win:
        with trace.op_span("chipbench.train"):
            state["w0"] = w0 = time.perf_counter()
            try:
                tr.run(10 ** 9)
            except _Stop:
                pass
            tr.ckpt.wait()
            window_s = time.perf_counter() - w0
    compiles = run.clock.count - compiles0
    n_steps = len(steps) - first
    c1 = CKPT_STATS.snapshot()
    memory = run.read_memory()
    commits = int(c1["commits"] - c0["commits"])
    state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(tr.state))
    phys1 = dir_bytes(ckdir)
    tokens = n_steps * batch * seq
    e2e = {"train_tokens_per_s": tokens / window_s,
           "stored_bytes_ratio": (phys1 - phys0) / (state_bytes * commits)}
    last = steps[-1] + 1
    log(f"window: {n_steps} steps to step {last}, {commits} commits "
        f"({int(c1['coalesced'] - c0['coalesced'])} coalesced) in "
        f"{window_s:.3f} s; {compiles} compiles inside it")

    # -- correctness, once the window has closed -------------------------------
    t0 = time.perf_counter()
    live = {k: np.asarray(v) for k, v in _flat(tr.state).items()}
    tr.state = None
    tr.ckpt.close()
    restored, step = CheckpointManager(ckdir, model_name=cfg.name).restore()
    differ = sum(1 for k, v in live.items()
                 if k not in restored or not _same_bits(restored[k], v))
    del live, restored
    log(f"restore compared in {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    mu1 = jax.device_get(mu1)
    params3 = jax.device_get(params3)
    params0, batches = reference_inputs(cell, run.seed, template)
    ref = reference_model(cell).train(params0, batches, cell.config["model"],
                                      mix["optimizer"],
                                      rows=int(mix["reference_rows"]))
    got = readings(losses, mu1, params3, ref, jax.device_get(params0),
                   mix["optimizer"]["b1"])
    log(f"reference in {time.perf_counter() - t0:.3f} s; readings {got}")
    lim = mix["limits"]
    checks = {"restored_leaves_differing": {"value": differ, "limit": 0},
              "restored_step_off": {"value": abs(step - last), "limit": 0},
              "loss_gap": {"value": got["loss_gap"],
                           "limit": lim["loss_gap"]},
              "grad_gap": {"value": got["grad_gap"],
                           "limit": lim["grad_gap"]},
              "change_gap": {"value": got["change_gap"],
                             "limit": lim["change_gap"]}}
    return {"setup_end": setup_end, "attempted": n_steps, "failed": 0,
            "e2e": e2e, "checks": checks, "memory": memory,
            "window_s": window_s, "capture": win,
            "record": {"steps": n_steps, "seq": seq, "batch": batch,
                       "commits": commits, "saves": n_steps // every,
                       "coalesced": int(c1["coalesced"] - c0["coalesced"]),
                       "compiles_in_window": compiles}}
