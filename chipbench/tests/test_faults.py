"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip, plants one fault in the
program (not in the benchmark), drives the rest of a run and reads
``correct``: for lineage cells an answer altered where it is produced, in a
checkout and in a commit; for the training cell a step that returns its
state unchanged, and a step that leaves out half of the batch.
"""

import jax
import jax.numpy as jnp

from helpers import args, rehearse


def _flip_first(x):
    x = jnp.asarray(x)
    flat = x.reshape(-1)
    uint = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(flat[:1], uint) ^ uint(1)
    return flat.at[:1].set(jax.lax.bitcast_convert_type(bits, x.dtype)
                           ).reshape(x.shape)


def test_checkout_answer_altered(tmp_path, monkeypatch):
    from repro.kernels import ops
    orig = ops.chain_apply
    monkeypatch.setattr(ops, "chain_apply",
                        lambda *a, **k: _flip_first(orig(*a, **k)))
    code, result, _ = rehearse(tmp_path, args("tiny.lineage-g2"))
    assert code == 0
    assert not result["correct"]
    assert result["checks"]["mismatched_elements"]["value"] > 0


def test_commit_answer_altered_in_the_chunk_layer(tmp_path, monkeypatch):
    from repro.store import delta
    orig = delta.host_snapshot

    def bad(p1, p2, eps):            # the chunk layer's quantizer
        q, nz, narrow = orig(p1, p2, eps)
        q = q.copy()
        q.reshape(-1)[0] += 1
        return q, nz, narrow
    monkeypatch.setattr("repro.store.artifact_store.host_snapshot", bad)
    code, result, _ = rehearse(tmp_path, args("tiny.lineage-g2"))
    assert code == 0
    assert not result["correct"]


def test_commit_answer_altered_in_the_kernel(tmp_path, monkeypatch):
    from repro.kernels import ops
    orig = ops.dequant_apply
    monkeypatch.setattr(ops, "dequant_apply",
                        lambda *a, **k: _flip_first(orig(*a, **k)))
    code, result, _ = rehearse(tmp_path, args("tiny.lineage-fanout"))
    assert code == 0
    assert not result["correct"]


def _patch_step(monkeypatch, make):
    import repro.train.loop as loop
    orig = loop.make_train_step
    monkeypatch.setattr(loop, "make_train_step",
                        lambda *a, **k: make(orig(*a, **k)))


def test_train_step_returns_state_unchanged(tmp_path, monkeypatch):
    def make(step):
        def unchanged(state, batch):
            _, metrics = step(jax.tree_util.tree_map(jnp.copy, state), batch)
            return state, metrics
        return unchanged
    _patch_step(monkeypatch, make)
    code, result, _ = rehearse(tmp_path, args("tiny.train-ckpt"))
    assert code == 0
    assert not result["correct"]
    assert result["checks"]["grad_gap"]["value"] > 0.5


def test_train_step_leaves_out_half_the_batch(tmp_path, monkeypatch):
    def make(step):
        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {"tokens": batch["tokens"][:n]})
        return half
    _patch_step(monkeypatch, make)
    code, result, _ = rehearse(tmp_path, args("tiny.train-ckpt"))
    assert code == 0
    assert not result["correct"]
