"""Serving pool (DESIGN.md §13.1): one resident base, delta-derived views,
content-hash aliasing, bit-identity against store truth, LRU eviction."""

import numpy as np
import pytest

from repro.core import LayerGraph, LayerNode, ModelArtifact
from repro.serve import BitIdentityError, ModelPool
from repro.store import ArtifactStore

from helpers import make_chain_model, perturb

# small grid so multi-chunk behavior shows on test-sized tensors
CHUNK_KW = dict(chunk_threshold=64 * 1024, chunk_min=16 * 1024,
                chunk_avg=32 * 1024, chunk_max=64 * 1024)


def seed_store(tmp_path, keys=("L0/w", "L3/w"), **kw):
    """Base model + one single-layer derivative per key, all delta-chained."""
    store = ArtifactStore(root=str(tmp_path), **kw)
    base = make_chain_model(seed=0)
    base_ref = store.commit_artifact("base", base)
    refs = [store.commit_artifact(f"d{i}", perturb(base, key, seed=10 + i),
                                  parent_ref=base_ref)
            for i, key in enumerate(keys)]
    return store, base, base_ref, refs


# ---------------------------------------------------------------------------
# bit-identity + aliasing
# ---------------------------------------------------------------------------

def test_pool_view_bit_identical_to_store_truth(tmp_path):
    store, base, base_ref, (r0, _) = seed_store(tmp_path)
    pool = ModelPool(store)
    view = pool.get(r0)
    truth = store.materialize_artifact(r0)
    assert set(view.params) == set(truth.params)
    for k in truth.params:
        np.testing.assert_array_equal(np.asarray(view.params[k]),
                                      np.asarray(truth.params[k]), err_msg=k)
    # only the perturbed tensor is private; everything else aliases the base
    assert "L0/w" not in view.aliased
    assert len(view.aliased) == len(truth.params) - 1
    assert view.private_bytes < pool.base_bytes
    s = pool.stats()
    assert s["params_aliased"] == len(view.aliased)
    assert s["bytes_aliased"] > 0
    assert s["params_applied"] == 1


def test_pool_aliases_share_memory_across_views(tmp_path):
    store, base, base_ref, (r0, r1) = seed_store(tmp_path)
    pool = ModelPool(store)
    v0, v1 = pool.get(r0), pool.get(r1)
    # unchanged tensors are the SAME resident array in every view
    assert v0.params["L1/w"] is v1.params["L1/w"]
    assert pool.stats()["resident"] == 2
    # two models resident for (far) less than two full copies
    assert pool.private_bytes() < pool.base_bytes


def test_pool_folded_chain_matches_store(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    cur = make_chain_model(seed=0)
    ref = store.commit_artifact("v0", cur)
    for i in range(1, 4):
        cur = perturb(cur, "L1/w", seed=i)
        ref = store.commit_artifact(f"v{i}", cur, parent_ref=ref)
    pool = ModelPool(store)
    view = pool.get(ref)
    truth = store.materialize_artifact(ref)
    for k in truth.params:
        np.testing.assert_array_equal(np.asarray(view.params[k]),
                                      np.asarray(truth.params[k]), err_msg=k)
    if store.get_manifest(ref)["depth"] == 3:
        s = pool.stats()
        assert s["chain_hops"] >= 3
        assert s["segments_applied"] >= 1


def test_pool_verify_catches_divergence(tmp_path, monkeypatch):
    store, base, base_ref, (r0, _) = seed_store(tmp_path)
    pool = ModelPool(store)
    pool.ensure_base(r0)
    bad = lambda *a, **k: np.zeros((1,), np.float32)  # noqa: E731
    monkeypatch.setattr(pool, "_apply_chain", bad)
    monkeypatch.setattr(store, "materialize_param", bad)
    with pytest.raises(BitIdentityError):
        pool.get(r0)


def test_pool_one_family_guard(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    ra = store.commit_artifact("a", make_chain_model(seed=0))
    rb = store.commit_artifact("b", make_chain_model(seed=7))
    pool = ModelPool(store)
    pool.get(ra)
    with pytest.raises(ValueError, match="one pool per model family"):
        pool.get(rb)


# ---------------------------------------------------------------------------
# chunked params through the pool (kind: chunked)
# ---------------------------------------------------------------------------

def test_pool_chunked_param_bit_identical(tmp_path):
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    rng = np.random.default_rng(0)
    big = rng.standard_normal((256, 300)).astype(np.float32)  # ~300 KB
    head = rng.standard_normal((300, 4)).astype(np.float32)
    g = LayerGraph.chain([
        LayerNode("big", "linear", params={"w": ((256, 300), "float32")}),
        LayerNode("head", "linear", params={"w": ((300, 4), "float32")}),
    ])
    base = ModelArtifact(g, {"big/w": big, "head/w": head})
    base_ref = store.commit_artifact("base", base)
    edited = big.copy()
    edited.reshape(-1)[:64] += 0.5
    ref = store.commit_artifact("d", base.replace_params({"big/w": edited}),
                                parent_ref=base_ref)
    assert store.get_manifest(ref)["params"]["big/w"]["kind"] == "chunked"
    pool = ModelPool(store)
    view = pool.get(ref)
    truth = store.materialize_artifact(ref)
    for k in truth.params:
        np.testing.assert_array_equal(np.asarray(view.params[k]),
                                      np.asarray(truth.params[k]), err_msg=k)
    # the untouched small param aliases; the chunked edit is verified private
    assert "head/w" in view.aliased
    assert "big/w" not in view.aliased
    assert pool.stats()["params_verified"] >= 1


# ---------------------------------------------------------------------------
# LRU + cache-eviction bit-neutrality
# ---------------------------------------------------------------------------

def test_pool_lru_eviction_and_hits(tmp_path):
    store, base, base_ref, refs = seed_store(
        tmp_path, keys=("L0/w", "L2/w", "L3/w"))
    pool = ModelPool(store, max_resident=2)
    pool.get(refs[0])
    pool.get(refs[0])
    assert pool.stats()["hits"] == 1
    pool.get(refs[1])
    pool.get(refs[2])
    assert len(pool.resident_refs) == 2
    assert refs[0] not in pool.resident_refs
    assert pool.stats()["evictions"] == 1
    # an evicted ref rebuilds on demand, bit-identical again
    view = pool.get(refs[0])
    truth = store.materialize_artifact(refs[0])
    np.testing.assert_array_equal(np.asarray(view.params["L0/w"]),
                                  np.asarray(truth.params["L0/w"]))


def test_pool_budget_evicts_private_bytes(tmp_path):
    store, base, base_ref, refs = seed_store(tmp_path)
    pool = ModelPool(store, budget_bytes=1)  # any private byte is over
    pool.get(refs[0])
    pool.get(refs[1])
    assert pool.resident_refs == [refs[1]]  # never evicts below one view
    assert pool.stats()["evictions"] == 1


def test_store_reload_picks_up_foreign_commits(tmp_path):
    """A long-running reader (serve daemon) sees another process's commit
    after ``reload()`` — the cross-process hot-swap path."""
    writer = ArtifactStore(root=str(tmp_path))
    base = make_chain_model(seed=0)
    base_ref = writer.commit_artifact("base", base)
    reader = ArtifactStore(root=str(tmp_path))  # snapshot of the index now
    ref = writer.commit_artifact("d", perturb(base, "L0/w", seed=3),
                                 parent_ref=base_ref)
    with pytest.raises(KeyError):
        reader.get_manifest(ref)
    reader.reload()
    view = ModelPool(reader).get(ref)
    truth = writer.materialize_artifact(ref)
    for k in truth.params:
        np.testing.assert_array_equal(np.asarray(view.params[k]),
                                      np.asarray(truth.params[k]), err_msg=k)


def test_pool_rebuild_after_cache_clear_is_bit_neutral(tmp_path):
    store, base, base_ref, (r0, r1) = seed_store(tmp_path)
    pool = ModelPool(store, max_resident=1)
    first = {k: np.asarray(v).copy()
             for k, v in pool.get(r0).params.items()}
    pool.get(r1)  # evicts r0's view
    assert pool.stats()["evictions"] == 1
    # drop the store's tensor + fold caches: the rebuild must come cold off
    # the CAS and still be byte-for-byte what the first build produced
    store.cache.clear()
    store.fold_cache.clear()
    again = pool.get(r0)
    assert pool.stats()["views_built"] == 3
    assert set(again.params) == set(first)
    for k, v in again.params.items():
        np.testing.assert_array_equal(np.asarray(v), first[k], err_msg=k)
