"""Yi-6B (arXiv:2403.04652) through the program against its plain
reference, and a bf16 Yi derivative through the store.

The program's ``yi-6b`` arch at a small size that keeps what makes the
block Yi's (grouped key/value heads at 8:1, SwiGLU, rotary theta 5e6), in
float32 with seeded random weights, gives the logits of
``chipbench/reference/dense_lm.forward``; a derivative committed against
its base in bfloat16, the published dtype, checks out bit for bit, so its
forward is the derivative's own.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import flat_paths, forward, get_config, init_params
from repro.models.model import _nested
from repro.store import ArtifactStore
from repro.store.checkpoint import spec_graph
from repro.core import ModelArtifact

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.reference import dense_lm  # noqa: E402

#: Float32 on both sides: the program's blocked attention, scans and
#: einsums order the float32 sums differently from the reference's plain
#: products, which moves the logits by a few ulps (about 6e-7 of the
#: largest at this size, on the CPU). The reference computed in bfloat16
#: misses by about 1e-2, so 2e-5 of the largest logit tells them apart.
LOGIT_TOL = 2e-5


def small_yi(dtype: str):
    return dataclasses.replace(
        get_config("yi-6b"), n_layers=2, d_model=256, n_heads=16,
        n_kv_heads=2, head_dim=16, d_ff=688, vocab_size=512, dtype=dtype,
        remat="none")


def model_dict(cfg):
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta}


def seeded_params(cfg, seed: int):
    """``init_params`` with the norms' scales drawn too (they start at 0,
    so a norm left at its init would test nothing)."""
    flat = flat_paths(init_params(cfg, seed=seed))
    rng = np.random.default_rng(seed)
    for k, v in flat.items():
        if k.rsplit("/", 1)[-1] in ("ln1", "ln2", "final_norm"):
            flat[k] = jnp.asarray(rng.normal(0, 0.1, v.shape), v.dtype)
    return _nested(flat)


def tokens(cfg, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 24)), jnp.int32)


def test_yi_arch_keeps_published_block():
    cfg = get_config("yi-6b")
    assert (cfg.n_heads // cfg.n_kv_heads, cfg.mlp_type, cfg.rope_theta,
            cfg.d_model, cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        8, "swiglu", 5e6, 4096, 128, 11008, 64000)
    small = small_yi("float32")
    assert (small.n_heads // small.n_kv_heads, small.mlp_type,
            small.rope_theta) == (8, "swiglu", 5e6)


@pytest.mark.parametrize("ref_dtype", ["float32", "bfloat16"])
def test_yi_logits_match_plain_reference(ref_dtype):
    """The program's logits against the reference's in float32 (within
    ``LOGIT_TOL``), and against the reference one precision lower (the
    control, which must miss)."""
    cfg = small_yi("float32")
    params = seeded_params(cfg, seed=0)
    toks = tokens(cfg)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(forward(cfg, params, {"tokens": toks}))
        want = np.asarray(dense_lm.forward(params, toks, model_dict(cfg),
                                           dtype=jnp.dtype(ref_dtype))
                          ).astype(np.float32)
    gap = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    if ref_dtype == "float32":
        assert gap <= LOGIT_TOL, gap
    else:
        assert gap > 50 * LOGIT_TOL, gap


def test_yi_bf16_derivative_checks_out_bit_for_bit(tmp_path):
    """A G2-style derivative of a bf16 Yi base, committed against it with
    the chunk threshold under its smallest leaf, so that every leaf takes
    the lossless chunk hops, checks out from a fresh store as the
    derivative itself: same bits, same logits."""
    cfg = small_yi("bfloat16")
    base = {k: np.asarray(v) for k, v in
            flat_paths(seeded_params(cfg, seed=1)).items()}
    rng = np.random.default_rng(2)
    child = {}
    for k, v in base.items():
        mask = rng.random(v.shape) < 0.1
        moved = v.astype(np.float32) + np.where(
            mask, rng.normal(0, 5e-3, v.shape), 0.0).astype(np.float32)
        child[k] = moved.astype(v.dtype)
    graph = spec_graph({k: (v.shape, str(v.dtype)) for k, v in base.items()},
                       "yi-6b")
    kw = dict(chunk_threshold=min(v.nbytes for v in base.values()),
              chunk_min=16 * 1024,
              chunk_avg=32 * 1024, chunk_max=64 * 1024)
    store = ArtifactStore(root=str(tmp_path), **kw)
    r0 = store.commit_artifact("base", ModelArtifact(graph, base,
                                                     model_type="yi-6b"))
    r1 = store.commit_artifact("ft", ModelArtifact(graph, child,
                                                   model_type="yi-6b"),
                               parent_ref=r0)
    entries = store.get_manifest(r1)["params"]
    assert all(e["kind"] == "chunked" for e in entries.values())
    assert all(any(it.get("x") for it in entries[k]["chunks"])
               for k in ("lm_head", "embed/tok", "layers/mlp/w_in"))
    got = dict(ArtifactStore(root=str(tmp_path), **kw)
               .materialize_artifact(r1).params)
    for k in child:
        assert got[k].tobytes() == child[k].tobytes(), k
    toks = tokens(cfg)
    want = forward(cfg, _nested({k: jnp.asarray(v)
                                 for k, v in child.items()}),
                   {"tokens": toks})
    out = forward(cfg, _nested({k: jnp.asarray(got[k]) for k in child}),
                  {"tokens": toks})
    assert np.asarray(out).tobytes() == np.asarray(want).tobytes()
