"""Throughput engines (DESIGN.md §10): pipelined commit, segment folding,
batched checkout, zero-copy pack I/O, durability fixes."""

import json
import os
import threading

import numpy as np
import pytest

from repro.store import CAS, ArtifactStore
from repro.store.delta import host_dequant, host_snapshot

from helpers import finetune_like, make_chain_model


def _build_chain(store, depth, seed0=0, d=32):
    model = make_chain_model(seed=seed0, d=d)
    refs = [store.commit_artifact("v0", model)]
    for v in range(1, depth + 1):
        model = finetune_like(model, seed=v)
        refs.append(store.commit_artifact(f"v{v}", model,
                                          parent_ref=refs[-1]))
    return refs, model


# ---------------------------------------------------------------------------
# host twins == jax ref kernels, bitwise (the fold's load-bearing identity)
# ---------------------------------------------------------------------------


def test_host_dequant_bit_identical_to_ref_kernel():
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    for eps in (1e-4, 1e-3, 5e-5):
        p1 = (rng.normal(size=(97, 53)) * rng.uniform(0.01, 50)
              ).astype(np.float32)
        q = rng.integers(-2000, 2000, size=p1.shape).astype(np.int32)
        ref = np.asarray(ops.dequant_apply(p1, q, eps=eps, backend="ref",
                                           out_dtype="float32"))
        np.testing.assert_array_equal(ref, host_dequant(p1, q, eps))


def test_host_snapshot_bit_identical_to_ref_kernel():
    from repro.kernels import ops
    rng = np.random.default_rng(1)
    for eps in (1e-4, 1e-3):
        p1 = (rng.normal(size=(64, 40)) * 3).astype(np.float32)
        p2 = (p1 + rng.normal(scale=rng.uniform(1e-6, 1e-2),
                              size=p1.shape)).astype(np.float32)
        qj, nzj, _fp, narrow_j = ops.snapshot_fused(p1, p2, eps=eps,
                                                    backend="ref")
        q, nz, narrow = host_snapshot(p1, p2, eps)
        assert nz == nzj and narrow == narrow_j
        np.testing.assert_array_equal(np.asarray(qj, np.int32),
                                      q.astype(np.int32))


# ---------------------------------------------------------------------------
# segment folding
# ---------------------------------------------------------------------------


def test_depth5_chain_folds_to_one_dequant(tmp_path):
    store = ArtifactStore(root=str(tmp_path), max_chain_depth=8)
    refs, final = _build_chain(store, 5)
    store.cache.clear()
    store.fold_cache.clear()
    store.reset_io_stats()
    v = store.materialize_param(refs[-1], "L0/w")
    io = store.io_stats
    assert io["chain_hops"] == 5          # every blob decoded
    assert io["dequant_calls"] == 1       # ...ONE dequant applied
    assert io["hops_folded"] == 4
    np.testing.assert_allclose(v, final.params["L0/w"], atol=5e-4)


def test_batch_equals_per_param_equals_recursive_bitwise(tmp_path):
    store = ArtifactStore(root=str(tmp_path), max_chain_depth=8)
    refs, _ = _build_chain(store, 6)
    store.cache.clear()
    store.fold_cache.clear()
    batch = store.materialize_artifact(refs[-1])
    store.cache.clear()
    store.fold_cache.clear()
    for k in batch.params:
        np.testing.assert_array_equal(np.asarray(batch.params[k]),
                                      store.materialize_param(refs[-1], k))
    recursive = store.load_artifact_recursive(refs[-1])
    for k in batch.params:
        np.testing.assert_array_equal(np.asarray(batch.params[k]),
                                      np.asarray(recursive.params[k]))


def test_fold_cache_eviction_cannot_change_bits(tmp_path):
    with_cache = ArtifactStore(root=str(tmp_path), max_chain_depth=8)
    refs, _ = _build_chain(with_cache, 5)
    warm = with_cache.materialize_artifact(refs[-1])  # fold states warm
    # a second store with NO fold cache (budget 0) folds cold from base
    no_cache = ArtifactStore(root=str(tmp_path), max_chain_depth=8,
                             fold_budget_bytes=0)
    cold = no_cache.materialize_artifact(refs[-1])
    for k in warm.params:
        np.testing.assert_array_equal(np.asarray(warm.params[k]),
                                      np.asarray(cold.params[k]))


def test_mixed_eps_chain_segments_and_stays_consistent(tmp_path):
    """eps changes mid-chain: folding must split segments (structural rule)
    and still agree bitwise across all three materialization paths."""
    store = ArtifactStore(root=str(tmp_path), max_chain_depth=8, eps=1e-4)
    model = make_chain_model(seed=0, d=32)
    refs = [store.commit_artifact("v0", model)]
    for v in range(1, 3):
        model = finetune_like(model, seed=v)
        refs.append(store.commit_artifact(f"v{v}", model,
                                          parent_ref=refs[-1]))
    store.eps = 1e-3  # reconfigured store keeps committing onto the chain
    for v in range(3, 5):
        model = finetune_like(model, seed=v)
        refs.append(store.commit_artifact(f"v{v}", model,
                                          parent_ref=refs[-1]))

    store.cache.clear()
    store.fold_cache.clear()
    store.reset_io_stats()
    tip = store.materialize_param(refs[-1], "L0/w")
    io = store.io_stats
    assert io["chain_hops"] == 4
    assert io["dequant_calls"] == 2       # one per same-eps segment
    np.testing.assert_allclose(tip, model.params["L0/w"], atol=5e-3)

    store.cache.clear()
    store.fold_cache.clear()
    batch = store.materialize_artifact(refs[-1])
    recursive = store.load_artifact_recursive(refs[-1])
    for k in batch.params:
        np.testing.assert_array_equal(np.asarray(batch.params[k]),
                                      np.asarray(recursive.params[k]))
    np.testing.assert_array_equal(np.asarray(batch.params["L0/w"]), tip)


def test_reopened_store_reproduces_committed_hashes(tmp_path):
    """Stored truth round-trips: manifest hash fields match what a fresh
    store materializes (commit fold == checkout fold)."""
    from repro.common.hashing import tensor_hash
    store = ArtifactStore(root=str(tmp_path), max_chain_depth=8)
    refs, _ = _build_chain(store, 4)
    fresh = ArtifactStore(root=str(tmp_path), max_chain_depth=8)
    manifest = fresh.get_manifest(refs[-1])
    for key, e in manifest["params"].items():
        value = fresh.materialize_param(refs[-1], key)
        assert tensor_hash(np.asarray(value)) == e["hash"], key


# A seeded depth-4 chain with pinned bits. The float32 leaves take two
# same-eps segments (1e-4, 1e-4 | 1e-3, 1e-3); the bf16 leaf takes one
# quantized hop per commit, none of which folds. Any change to how a
# chain is committed or rebuilt moves the tip's ref or a hash below.
GOLDEN_LEAVES = {"L0/w": ((24, 24), "float32"), "L0/b": ((24,), "float32"),
                 "L1/w": ((24, 24), "bfloat16"),
                 "head/w": ((24, 4), "float32")}
GOLDEN_TIP = ("m_79e5418660dcd11a7cd8c8ab24aef98186e035fe714b42b62cd"
              "05812f997022c")
GOLDEN_HASHES = {
    "L0/b": "013152f54d879c975ca407cdca8fbd4f890e1ad31217c50d36f857982300851b",
    "L0/w": "cb1bd2e4cdb244f3d4c4171b71bbb6e4d5214cb92702461f3d3da2a6d3420026",
    "L1/w": "1facccbba21974241996a5e50c24177a91131f5fe4fdfb3f5437f3f165f5166d",
    "head/w": "8b0986d601cd083e0f15b9f53edea0ed45590313aed1b77334281c0095"
              "8bd1cd",
}


def _golden_chain(root):
    import ml_dtypes  # noqa: F401  (registers bfloat16 with NumPy)
    from repro.core import LayerGraph, LayerNode, ModelArtifact
    rng = np.random.default_rng(1234)
    graph = LayerGraph.chain([
        LayerNode("L0", "linear", params={"w": GOLDEN_LEAVES["L0/w"],
                                          "b": GOLDEN_LEAVES["L0/b"]}),
        LayerNode("L1", "linear", params={"w": GOLDEN_LEAVES["L1/w"]}),
        LayerNode("head", "linear", params={"w": GOLDEN_LEAVES["head/w"]})])
    model = ModelArtifact(graph, {
        k: rng.normal(size=s).astype(np.dtype(dt))
        for k, (s, dt) in GOLDEN_LEAVES.items()}, model_type="toy")
    scale = {"float32": 1e-3, "bfloat16": 3e-2}
    store = ArtifactStore(root=root, backend="ref", eps=1e-4)
    ref = store.commit_artifact("v0", model)
    for v, eps in enumerate((1e-4, 1e-4, 1e-3, 1e-3), start=1):
        store.eps = eps
        model = model.map_params(lambda k, x: (
            x.astype(np.float32)
            + rng.normal(scale=scale[str(x.dtype)], size=x.shape)
        ).astype(x.dtype))
        ref = store.commit_artifact(f"v{v}", model, parent_ref=ref)
    return store, ref


def test_golden_chain_pins_tip_ref_and_hashes(tmp_path):
    from repro.common.hashing import tensor_hash
    from repro.serve import ModelPool
    store, tip = _golden_chain(str(tmp_path))
    assert tip == GOLDEN_TIP
    manifest = store.get_manifest(tip)
    assert manifest["depth"] == 4
    assert {k: e["hash"] for k, e in manifest["params"].items()} \
        == GOLDEN_HASHES
    for key in GOLDEN_HASHES:
        assert len(store.chain_recipe(tip, key)[3]) == 4, key

    # a cold store rebuilds the same bits: two dequants per float32 leaf
    # (one per segment), one per bf16 hop
    fresh = ArtifactStore(root=str(tmp_path), backend="ref")
    fresh.reset_io_stats()
    got = fresh.materialize_artifact(tip)
    assert {k: tensor_hash(np.asarray(v)) for k, v in got.params.items()} \
        == GOLDEN_HASHES
    io = fresh.io_stats
    assert io["chain_hops"] == 16
    assert io["hops_folded"] == 6
    assert io["dequant_calls"] == 3 * 2 + 4

    # the serving pool rebuilds them over its resident base and checks
    # every one against the manifest (BitIdentityError otherwise); on a
    # device backend each two-hop segment is one fused chain_apply
    for backend, fused in (("ref", 0), ("interpret", 6)):
        pool = ModelPool(fresh, backend=backend)
        pool.get(tip)
        s = pool.stats()
        assert s["params_verified"] == 4
        assert s["chain_hops"] == 16
        assert s["segments_applied"] == 6
        assert s["fused_applies"] == fused


def _rewrite_truth_marker(root, truth):
    """Set (or, with ``truth=None``, drop) store_stats.json's marker."""
    stats_path = os.path.join(root, "store_stats.json")
    with open(stats_path) as f:
        payload = json.load(f)
    payload.pop("truth")
    if truth is not None:
        payload["truth"] = truth
    with open(stats_path, "w") as f:
        json.dump(payload, f)


def test_truth_marker_rejects_mismatched_reopen(tmp_path):
    """One reconstruction truth (§10.2): a repository whose store_stats.json
    records another truth (older repositories wrote the hop-by-hop one)
    is refused at open, instead of materializing bits its manifest hashes
    do not certify."""
    store = ArtifactStore(root=str(tmp_path))
    _build_chain(store, 2)
    _rewrite_truth_marker(str(tmp_path), "hop-by-hop")
    with pytest.raises(ValueError, match="reconstruction truth") as err:
        ArtifactStore(root=str(tmp_path))
    assert str(tmp_path) in str(err.value)


def test_repo_without_truth_marker_is_refused(tmp_path):
    """A store_stats.json with no truth marker predates it: its chains may
    certify hop-by-hop bits, so the open fails rather than guessing."""
    store = ArtifactStore(root=str(tmp_path))
    _build_chain(store, 2)
    _rewrite_truth_marker(str(tmp_path), None)
    with pytest.raises(ValueError, match="reconstruction truth") as err:
        ArtifactStore(root=str(tmp_path))
    assert str(tmp_path) in str(err.value)


def test_pipelined_commit_respects_accuracy_gate(tmp_path):
    from repro.core.lineage import RegisteredTest
    store = ArtifactStore(root=str(tmp_path), t_thr=0.0, eps=10.0)
    parent = make_chain_model(seed=0)
    child = finetune_like(parent, seed=1, scale=1e-2, density=1.0)
    r1 = store.commit_artifact("p", parent)
    probe = RegisteredTest(name="l2", model_type="toy",
                           fn=lambda m: float(np.linalg.norm(
                               np.asarray(m.params["L0/w"], np.float64))))
    r2 = store.commit_artifact("c", child, parent_ref=r1, tests=[probe])
    # huge eps + zero tolerance: compression must be rejected -> full commit
    assert store.get_manifest(r2)["depth"] == 0
    assert all(e["kind"] == "full"
               for e in store.get_manifest(r2)["params"].values())


# ---------------------------------------------------------------------------
# durability + miss-path satellites
# ---------------------------------------------------------------------------


def test_write_loose_fsyncs_before_replace(tmp_path, monkeypatch):
    """Crash-sim regression: the tmp file must be fsynced BEFORE os.replace
    publishes it — otherwise a crash can leave a truncated object under its
    content-addressed (i.e. trusted) name."""
    events = []
    real_fsync, real_replace = os.fsync, os.replace

    def spy_fsync(fd):
        events.append(("fsync",))
        return real_fsync(fd)

    def spy_replace(src, dst):
        if str(src).endswith(".tmp"):
            events.append(("replace", os.path.basename(dst)))
        return real_replace(src, dst)

    monkeypatch.setattr(os, "fsync", spy_fsync)
    monkeypatch.setattr(os, "replace", spy_replace)
    cas = CAS(str(tmp_path), pack_threshold=16)
    key = cas.put_bytes(os.urandom(4096))
    replace_i = next(i for i, e in enumerate(events)
                     if e[0] == "replace" and e[1] == key)
    assert ("fsync",) in events[:replace_i], events


def test_get_bytes_missing_key_is_keyerror(tmp_path):
    cas = CAS(str(tmp_path))
    for fn in (cas.get_bytes, cas.get_view):
        with pytest.raises(KeyError):
            fn("deadbeef" * 8)
    mem = CAS(None)
    with pytest.raises(KeyError):
        mem.get_bytes("deadbeef" * 8)


def test_loose_overwrite_invalidates_mmap_pool(tmp_path):
    """Overwrite-in-place of a loose object (forced diag ledger re-record
    whose payload crossed the pack threshold) swaps the inode — a pooled
    map of the old file must not keep serving the superseded bytes."""
    cas = CAS(str(tmp_path), pack_threshold=16)
    key = "t_demo_ledger_entry"
    cas.put_bytes(b"A" * 4096, key=key)
    assert cas.get_bytes(key) == b"A" * 4096  # maps the file
    cas.put_bytes(b"B" * 4096, key=key, overwrite=True)
    assert cas.get_bytes(key) == b"B" * 4096
    assert bytes(cas.get_view(key)) == b"B" * 4096


def test_batch_single_fsync_per_pack(tmp_path):
    cas = CAS(str(tmp_path), pack_threshold=4096)
    with cas.batch():
        keys = [cas.put_bytes(os.urandom(200)) for _ in range(64)]
        # records must be readable mid-batch (handle flushed per record)
        assert cas.get_bytes(keys[0])
    assert cas.stats["fsyncs"] == 1  # one pack, one fsync at the commit point
    cas.flush()
    reopened = CAS(str(tmp_path), pack_threshold=4096)
    for k in keys:
        assert len(reopened.get_bytes(k)) == 200


def test_zero_copy_get_tensor_is_readonly_view(tmp_path):
    cas = CAS(str(tmp_path), pack_threshold=1024)
    x = np.arange(8192, dtype=np.float32).reshape(128, 64)
    key = cas.put_tensor(x)
    before = cas.stats["zero_copy_gets"]
    y = cas.get_tensor(key)
    np.testing.assert_array_equal(x, y)
    assert not y.flags.writeable           # aliases the shared mmap
    assert y.base is not None              # a view, not an owned copy
    assert cas.stats["zero_copy_gets"] > before


def test_lzma_preset_knob_roundtrips_across_presets(tmp_path):
    """Blobs are container-self-describing: a store tuned to any preset
    reads chains written by any other."""
    fast = ArtifactStore(root=str(tmp_path), lzma_preset=0)
    refs, final = _build_chain(fast, 2)
    strong = ArtifactStore(root=str(tmp_path), lzma_preset=6)
    model = finetune_like(final, seed=9)
    ref3 = strong.commit_artifact("v3", model, parent_ref=refs[-1])
    fresh = ArtifactStore(root=str(tmp_path))  # default preset
    loaded = fresh.materialize_artifact(ref3)
    for k in loaded.params:
        np.testing.assert_allclose(np.asarray(loaded.params[k]),
                                   model.params[k], atol=5e-4)


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------


def test_concurrent_commits_keep_store_consistent(tmp_path):
    """Many threads committing different children of one base through the
    batched writer: counters, refcounts and fsck must all stay exact."""
    store = ArtifactStore(root=str(tmp_path), max_chain_depth=8)
    base = make_chain_model(seed=0, d=32)
    base_ref = store.commit_artifact("base", base)
    refs, errors = [], []

    def commit_one(i):
        try:
            child = finetune_like(base, seed=100 + i)
            refs.append(store.commit_artifact(f"c{i}", child,
                                              parent_ref=base_ref))
        except Exception as exc:  # pragma: no cover - failure detail
            errors.append(exc)

    threads = [threading.Thread(target=commit_one, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(set(refs)) == 6

    report = store.fsck(roots=[base_ref] + refs)
    assert report["ok"], report
    # O(1) counters agree with a fresh rebuild from disk
    reopened = ArtifactStore(root=str(tmp_path))
    assert reopened.cas.object_count() == store.cas.object_count()
    assert reopened.cas.physical_bytes() == store.cas.physical_bytes()
    assert reopened.fsck(roots=[base_ref] + refs)["ok"]
    # every child materializes bit-identically from both instances
    for r in refs:
        a = store.materialize_artifact(r)
        b = reopened.materialize_artifact(r)
        for k in a.params:
            np.testing.assert_array_equal(np.asarray(a.params[k]),
                                          np.asarray(b.params[k]))


def test_fsck_clean_after_pipelined_commit_gc_compaction(tmp_path):
    store = ArtifactStore(root=str(tmp_path), max_chain_depth=8,
                          pack_threshold=512)
    refs, _ = _build_chain(store, 6, d=16)
    # drop some mid-chain refs (lineage still holds chain deps), gc+compact
    extra = store.commit_artifact("spare", make_chain_model(seed=42, d=16))
    store.release(extra)
    store.gc()
    assert store.fsck(roots=refs)["ok"]
    reopened = ArtifactStore(root=str(tmp_path), pack_threshold=512)
    assert reopened.fsck(roots=refs)["ok"]


# ---------------------------------------------------------------------------
# batched checkout surface
# ---------------------------------------------------------------------------


def test_materialize_artifact_subset_and_cache_seeding(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    refs, final = _build_chain(store, 3)
    store.cache.clear()
    store.fold_cache.clear()
    sub = store.materialize_artifact(refs[-1], keys=["L0/w", "L1/w"])
    assert set(sub.params) == {"L0/w", "L1/w"}
    # batch checkout seeds the tensor cache: lazy access is now free
    store.reset_io_stats()
    lazy = store.load_artifact(refs[-1])
    np.testing.assert_array_equal(np.asarray(lazy.params["L0/w"]),
                                  np.asarray(sub.params["L0/w"]))
    assert store.io_stats["tensors_materialized"] == 0


def test_load_artifact_eager_routes_through_batch_engine(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    refs, final = _build_chain(store, 2)
    eager = store.load_artifact(refs[-1], lazy=False)
    assert not eager.is_lazy
    for k in final.params:
        np.testing.assert_allclose(np.asarray(eager.params[k]),
                                   final.params[k], atol=5e-4)
