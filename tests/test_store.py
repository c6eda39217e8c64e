"""Storage layer: CAS dedup/refcount/GC, codecs, delta compression chains."""

import numpy as np
import pytest

from hyp_compat import given, settings, st

from repro.core import LineageGraph
from repro.store import (CAS, CODECS, ArtifactStore, delta_compression,
                         lcs_param_matching)
from repro.core.lineage import RegisteredTest

from helpers import finetune_like, l2_test, make_chain_model, prune_like


# ---------------------------------------------------------------------------
# CAS
# ---------------------------------------------------------------------------

def test_cas_dedup(tmp_path):
    cas = CAS(str(tmp_path))
    x = np.arange(1000, dtype=np.float32)
    k1 = cas.put_tensor(x)
    k2 = cas.put_tensor(x.copy())
    assert k1 == k2
    assert cas.stats["dedup_hits"] == 1
    assert cas.object_count() == 1
    np.testing.assert_array_equal(cas.get_tensor(k1), x)


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
def test_cas_tensor_keeps_ml_dtype(tmp_path, dtype):
    """A bf16 train state committed full must read back as bf16, not as
    raw void bytes — restore casts to the template's dtype, and fsck and
    the pool re-derive the truth hash over (shape, dtype, bytes)."""
    import ml_dtypes
    cas = CAS(str(tmp_path), pack_threshold=0)
    x = np.linspace(-2, 2, 3000, dtype=np.float32).reshape(30, 100)
    x = x.astype(getattr(ml_dtypes, dtype))
    k = cas.put_tensor(x)
    cas.flush()
    back = CAS(str(tmp_path)).get_tensor(k)
    assert back.dtype == x.dtype and back.shape == x.shape
    np.testing.assert_array_equal(back.view(np.uint8), x.view(np.uint8))
    assert CAS(str(tmp_path)).fsck()["corrupt"] == []


def test_cas_refcount_gc(tmp_path):
    cas = CAS(str(tmp_path))
    x = np.ones(100, np.float32)
    k = cas.put_tensor(x)
    cas.put_tensor(x)          # refcount 2
    cas.decref(k)
    assert cas.gc() == 0       # still referenced
    cas.decref(k)
    assert cas.gc() > 0
    assert not cas.has(k)


def test_cas_memory_backend():
    cas = CAS(None)
    k = cas.put_bytes(b"hello")
    assert cas.get_bytes(k) == b"hello"
    assert cas.physical_bytes() == 5


# ---------------------------------------------------------------------------
# codecs (hypothesis roundtrips)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", sorted(CODECS))
@given(data=st.lists(st.integers(-2**31, 2**31 - 1), max_size=200),
       runs=st.lists(st.tuples(st.integers(-5, 5), st.integers(1, 50)),
                     max_size=20))
@settings(max_examples=25, deadline=None)
def test_codec_roundtrip(codec, data, runs):
    arr = np.array(data + [v for v, n in runs for _ in range(n)],
                   dtype=np.int32)
    c = CODECS[codec]
    out = c.decode(c.encode(arr), arr.size)
    np.testing.assert_array_equal(out, arr)


def test_codecs_compress_sparse_runs():
    arr = np.zeros(100000, np.int32)
    arr[::997] = 3
    for name in ("rle", "lzma", "zlib", "sparse"):
        assert len(CODECS[name].encode(arr)) < arr.nbytes / 5, name


# ---------------------------------------------------------------------------
# LCS parameter matching
# ---------------------------------------------------------------------------

def test_lcs_identical_architectures():
    a = make_chain_model(seed=0)
    b = make_chain_model(seed=1)
    pairs = lcs_param_matching(a, b)
    assert pairs == [(k, k) for k, _ in pairs]
    assert len(pairs) == len(a.params)


def test_lcs_differing_architectures():
    a = make_chain_model(seed=0, n_layers=4)
    b = make_chain_model(seed=1, n_layers=6)  # two extra layers
    pairs = lcs_param_matching(a, b)
    assert len(pairs) == len(a.params)  # all of a's params matched
    assert all(np.shape(a.params[p]) == np.shape(b.params[c])
               for p, c in pairs)


# ---------------------------------------------------------------------------
# delta compression (Algorithm 1)
# ---------------------------------------------------------------------------

@given(scale=st.floats(1e-6, 1e-4), density=st.floats(0.0, 0.5),
       seed=st.integers(0, 100))
@settings(max_examples=15, deadline=None)
def test_delta_error_bound_property(scale, density, seed):
    """Reconstruction error is bounded by the quantization step (~eps)."""
    parent = make_chain_model(seed=0)
    child = finetune_like(parent, seed=seed, scale=scale, density=density)
    res = delta_compression(child, parent, eps=1e-4, codec="zlib")
    for k in child.params:
        err = np.max(np.abs(res.reconstructed.params[k] - child.params[k]))
        assert err <= 2 * np.log1p(1e-4)  # one quantization step


def test_delta_rejected_for_unrelated():
    parent = make_chain_model(seed=0)
    child = make_chain_model(seed=99)  # totally different values
    res = delta_compression(child, parent, codec="lzma", per_param=True)
    # dense large deltas shouldn't beat raw storage meaningfully
    assert res.ratio < 2.0


def test_delta_accuracy_gate():
    parent = make_chain_model(seed=0)
    child = finetune_like(parent, seed=1)
    tests = [RegisteredTest(name="l2", fn=l2_test, model_type="toy")]
    res = delta_compression(child, parent, t_thr=0.0, eps=0.5,  # huge eps
                            codec="lzma", tests=tests)
    assert not res.accepted  # big eps wrecks the test score -> rejected


def test_delta_whole_model_mode():
    parent = make_chain_model(seed=0)
    child = finetune_like(parent, seed=1)
    res = delta_compression(child, parent, per_param=False, codec="lzma")
    assert res.accepted
    assert res.ratio > 3


# ---------------------------------------------------------------------------
# ArtifactStore: dedup + recursive chains + GC
# ---------------------------------------------------------------------------

def test_store_dedup_identical_models(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    m = make_chain_model(seed=0, d=128)
    store.commit_artifact("a", m)
    twin = make_chain_model(seed=0, d=128)
    store.commit_artifact("b", twin)
    assert store.compression_ratio() > 1.9  # second copy ~free


def test_store_delta_chain_roundtrip(tmp_path):
    store = ArtifactStore(root=str(tmp_path), codec="lzma")
    g = LineageGraph(path=str(tmp_path), store=store)
    m = make_chain_model(seed=0, d=64)
    g.add_node(m, "v1")
    cur = m
    prev = "v1"
    for v in range(2, 6):  # chain of 4 deltas
        cur = finetune_like(cur, seed=v)
        name = f"v{v}"
        g.add_node(None, name, model_type="toy")
        g.add_version_edge(prev, name)
        g._attach_artifact(g.nodes[name], cur)
        prev = name
    loaded = g.get_model("v5")
    for k in cur.params:
        assert np.max(np.abs(loaded.params[k] - cur.params[k])) < 5 * 1e-4
    assert store.compression_ratio() > 2.5


def test_store_chain_depth_cap(tmp_path):
    store = ArtifactStore(root=str(tmp_path), max_chain_depth=2)
    g = LineageGraph(path=str(tmp_path), store=store)
    m = make_chain_model(seed=0)
    g.add_node(m, "v1")
    prev, cur = "v1", m
    for v in range(2, 6):
        cur = finetune_like(cur, seed=v)
        name = f"v{v}"
        g.add_node(None, name, model_type="toy")
        g.add_version_edge(prev, name)
        g._attach_artifact(g.nodes[name], cur)
        prev = name
    depths = [store.get_manifest(g.nodes[f"v{v}"].artifact_ref)["depth"]
              for v in range(1, 6)]
    assert max(depths) <= 2


def test_store_release_and_gc(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    g = LineageGraph(path=str(tmp_path), store=store)
    g.add_node(make_chain_model(seed=0), "a")
    g.add_node(make_chain_model(seed=123), "b")
    before = store.cas.object_count()
    g.remove_node("b")
    store.gc()
    assert store.cas.object_count() < before
    # "a" still loads
    assert g.get_model("a").params["L0/w"].shape == (16, 16)


def test_pruned_models_preserve_sparsity(tmp_path):
    """G4 regime: quantize-then-delta must keep zeros exactly zero."""
    dense = make_chain_model(seed=0)
    pruned = prune_like(dense, sparsity=0.6)
    res = delta_compression(pruned, dense, codec="lzma", eps=1e-4)
    for k in pruned.params:
        rec = res.reconstructed.params[k]
        orig_zero = pruned.params[k] == 0
        # reconstruction of zeros stays within one quant step of zero
        assert np.max(np.abs(rec[orig_zero])) <= 2 * np.log1p(1e-4)
