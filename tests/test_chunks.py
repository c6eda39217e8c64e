"""Chunk layer (DESIGN.md §12): CDC boundaries, chunked commit/checkout,
chunk-granular dedup/fsck/sync, shard-scoped fetch, ranged transfer."""

import json
import os
import sys
import threading

import numpy as np
import pytest

from repro.core import LayerGraph, LayerNode, LineageGraph, ModelArtifact
from repro.obs import export_chrome_trace, reset_trace, tracing
from repro.store import ArtifactStore, CAS
from repro.store import chunks as chunklib
from repro.common.hashing import bytes_hash, tensor_hash
from repro.remote.sync import fetch_objects, fetch_param_shard
from repro.remote.transport import LocalTransport

# small grid so multi-chunk behavior shows on test-sized tensors
CHUNK_KW = dict(chunk_threshold=64 * 1024, chunk_min=16 * 1024,
                chunk_avg=32 * 1024, chunk_max=64 * 1024)


def big_artifact(seed=0, rows=256, cols=300, dtype="float32"):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((rows, cols)).astype(dtype)
    g = LayerGraph.chain([LayerNode("big", "linear",
                                    params={"w": ((rows, cols), dtype)})])
    return ModelArtifact(g, {"big/w": w}), w


def edit(w, frac=0.001, seed=1):
    """Localized edit touching ``frac`` of the elements."""
    rng = np.random.default_rng(seed)
    out = w.copy()
    n = max(1, int(w.size * frac))
    start = rng.integers(0, w.size - n)
    out.reshape(-1)[start:start + n] += 0.5
    return out


# ---------------------------------------------------------------------------
# content-defined chunking
# ---------------------------------------------------------------------------

def _mem_read(data):
    return lambda off, n: data[off:off + n]


def test_cut_points_invariants():
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=500_000, dtype=np.uint8).tobytes()
    cuts = chunklib.cut_points(_mem_read(data), len(data), 4,
                               min_size=8 * 1024, avg_size=16 * 1024,
                               max_size=64 * 1024, mode="cdc", segments=None)
    assert cuts[-1] == len(data)
    assert cuts == sorted(set(cuts))
    spans = chunklib.spans_of(cuts)
    for off, n in spans[:-1]:           # last chunk may undershoot min
        assert 8 * 1024 <= n <= 64 * 1024
        assert n % 4 == 0               # itemsize-aligned
    # deterministic: same bytes, same grid
    assert cuts == chunklib.cut_points(
        _mem_read(data), len(data), 4, min_size=8 * 1024,
        avg_size=16 * 1024, max_size=64 * 1024, mode="cdc", segments=None)


def test_cut_points_boundary_stability_under_prefix_shift():
    """The CDC property: content far from an insertion keeps its cuts."""
    rng = np.random.default_rng(1)
    tail = rng.integers(0, 256, size=400_000, dtype=np.uint8).tobytes()
    a = rng.integers(0, 256, size=64, dtype=np.uint8).tobytes() + tail
    b = rng.integers(0, 256, size=4096, dtype=np.uint8).tobytes() + tail
    kw = dict(min_size=8 * 1024, avg_size=16 * 1024, max_size=64 * 1024,
              mode="cdc", segments=None)
    cuts_a = chunklib.cut_points(_mem_read(a), len(a), 1, **kw)
    cuts_b = chunklib.cut_points(_mem_read(b), len(b), 1, **kw)
    # cuts are content-anchored: tail cuts realign modulo the shift
    tail_a = {c - 64 for c in cuts_a if c > 70_000}
    tail_b = {c - 4096 for c in cuts_b if c > 70_000}
    common = tail_a & tail_b
    assert len(common) >= 0.8 * max(1, len(tail_a))


def _reference_cut_points(data, itemsize, min_size, avg_size, max_size,
                          segments):
    """The boundary rule stated plainly: per cut, hash a max_size
    lookahead with the full 64-bit Gear hash, take the first candidate at
    or past min_size, else cut at max_size. Repositories written with it
    must keep their grids, so cut_points must give exactly these cuts."""
    gear, w = chunklib._GEAR, chunklib.WINDOW
    mask = np.uint64(avg_size - 1)

    def snap(off):
        return off // itemsize * itemsize

    min_size = max(itemsize, snap(min_size) or itemsize)
    max_size = max(min_size + itemsize, snap(max_size))
    bounds = sorted({0, len(data), *[s for s in segments
                                     if 0 < s < len(data)]})
    cuts = []
    for start, end in zip(bounds[:-1], bounds[1:]):
        pos = 0
        while end - start - pos > max_size:
            win = np.frombuffer(data[start + pos:start + pos + max_size],
                                np.uint8)
            h = gear[0][win[w - 1:]]
            for j in range(1, w):
                h ^= gear[j][win[w - 1 - j:win.size - j]]
            cut = max(itemsize, snap(max_size))
            for c in np.flatnonzero((h & mask) == 0) + w - 1:
                if snap(int(c) + 1) >= min_size:
                    cut = snap(int(c) + 1)
                    break
            if end - start - (pos + cut) < itemsize:
                break
            pos += cut
            cuts.append(start + pos)
        cuts.append(end)
    return sorted(set(c for c in cuts if 0 < c <= len(data)))


@pytest.mark.parametrize("content,itemsize,sizes,segments", [
    ("random", 1, (8 * 1024, 16 * 1024, 64 * 1024), ()),
    ("floats", 4, (4, 4096, 8192), (100_003, 300_000)),
    ("runs", 2, (1024, 65536, 4 * 1024), ()),
    ("floats", 8, (256 * 1024, 1 << 20, 4 << 20), ()),
])
def test_cut_points_match_reference_rule(content, itemsize, sizes, segments):
    rng = np.random.default_rng(7)
    n = 3_000_000 if sizes[2] > 1 << 20 else 600_000
    if content == "random":
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
    elif content == "floats":
        data = rng.standard_normal(n // 4).astype(np.float32).tobytes()
    else:
        data = np.repeat(rng.integers(0, 256, n // 100, dtype=np.uint8),
                         100).tobytes()
    min_size, avg_size, max_size = sizes
    got = chunklib.cut_points(_mem_read(data), len(data), itemsize,
                              min_size=min_size, avg_size=avg_size,
                              max_size=max_size, mode="cdc",
                              segments=list(segments) or None)
    assert got == _reference_cut_points(data, itemsize, min_size, avg_size,
                                        max_size, segments)


def test_segments_are_hard_cuts():
    data = bytes(range(256)) * 2048          # 512 KiB, highly regular
    seg = [200_000, 400_000]
    cuts = chunklib.cut_points(_mem_read(data), len(data), 4,
                               min_size=8 * 1024, avg_size=16 * 1024,
                               max_size=64 * 1024, mode="fixed",
                               segments=seg)
    assert set(seg) <= set(cuts)


def test_fixed_mode_grid():
    data = bytes(1_000_000)
    cuts = chunklib.cut_points(_mem_read(data), len(data), 4,
                               min_size=8 * 1024, avg_size=32 * 1024,
                               max_size=64 * 1024, mode="fixed",
                               segments=None)
    spans = chunklib.spans_of(cuts)
    assert all(n == 32 * 1024 for _, n in spans[:-1])
    assert sum(n for _, n in spans) == len(data)


# ---------------------------------------------------------------------------
# chunked commit / checkout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_commit_checkout_bit_identity(tmp_path, dtype):
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, w = big_artifact(dtype=dtype)
    ref = store.commit_artifact("m", art)
    e = store.get_manifest(ref)["params"]["big/w"]
    assert e["kind"] == "chunked" and len(e["chunks"]) > 1
    assert e["hash"] == tensor_hash(w)
    got = store.materialize_param(ref, "big/w")
    np.testing.assert_array_equal(got, w)
    # the lazy-load path and the recursive path agree
    lazy = store.load_artifact(ref)
    assert lazy.params.spec_of("big/w") == (w.shape, dtype)
    np.testing.assert_array_equal(np.asarray(lazy.params["big/w"]), w)


def test_chunked_dedup_on_small_edit(tmp_path):
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, w = big_artifact()
    r1 = store.commit_artifact("m", art)
    before = store.cas.physical_bytes()
    w2 = edit(w, frac=0.001)
    art2 = ModelArtifact(art.graph, {"big/w": w2})
    r2 = store.commit_artifact("m", art2, parent_ref=r1)
    added = store.cas.physical_bytes() - before
    assert added < 0.05 * w.nbytes, f"0.1% edit re-stored {added} bytes"
    np.testing.assert_array_equal(
        store.materialize_param(r2, "big/w"),
        store._materialize_chunked(r2, "big/w"))
    e2 = store.get_manifest(r2)["params"]["big/w"]
    kinds = {("c" if "c" in it else "b" if "b" in it else "p")
             for it in e2["chunks"]}
    assert e2.get("parent_ref") == r1
    assert "c" in kinds or "p" in kinds   # untouched chunks were not re-sent


def test_chunked_streaming_and_range(tmp_path):
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, w = big_artifact()
    ref = store.commit_artifact("m", art)
    raw = w.tobytes()
    # stream covers the tensor in order
    got = bytearray(len(raw))
    for off, data in store.stream_param(ref, "big/w"):
        got[off:off + len(data)] = data
    assert bytes(got) == raw
    # file checkout digest equals the entry hash (bit-identity marker)
    path = str(tmp_path / "w.bin")
    digest = store.materialize_param_to_file(ref, "big/w", path)
    assert digest == store.get_manifest(ref)["params"]["big/w"]["hash"]
    with open(path, "rb") as f:
        assert f.read() == raw
    # arbitrary byte range
    assert store.materialize_param_range(ref, "big/w", 100, 70_000) == \
        raw[100:70_000]


def test_chunked_release_gc_leaves_nothing(tmp_path):
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, w = big_artifact()
    r1 = store.commit_artifact("m", art)
    art2 = ModelArtifact(art.graph, {"big/w": edit(w)})
    r2 = store.commit_artifact("m", art2, parent_ref=r1)
    store.release(r2)
    store.release(r1)
    store.cas.gc()
    assert store.cas.object_count() == 0


def test_sub_threshold_params_unchanged(tmp_path):
    """Small tensors never chunk; chunking off reproduces the old layout."""
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, _ = big_artifact(rows=16, cols=16)   # 1 KiB, far below threshold
    ref = store.commit_artifact("m", art)
    assert store.get_manifest(ref)["params"]["big/w"]["kind"] == "full"
    off = ArtifactStore(root=str(tmp_path / "off"), chunk_threshold=0)
    art2, _ = big_artifact()
    ref2 = off.commit_artifact("m", art2)
    assert off.get_manifest(ref2)["params"]["big/w"]["kind"] == "full"


# ---------------------------------------------------------------------------
# the commit's chunk stream: one bounded, in-order stream over every leaf
# ---------------------------------------------------------------------------

# a/w stays frozen along the chain, d/w changes below the quantization step
# (pass-through chunks), f/w is under the chunk threshold
STREAM_LEAVES = {"a/w": (200, 200), "b/w": (256, 300), "c/w": (96, 256),
                 "d/w": (130, 333), "e/w": (400, 200), "f/w": (16, 16)}
CHUNKED = [k for k in STREAM_LEAVES if k != "f/w"]


def _stream_graph():
    return LayerGraph.chain([
        LayerNode(k.split("/")[0], "linear", params={"w": (s, "float32")})
        for k, s in STREAM_LEAVES.items()])


def _stream_finetune(params, seed):
    rng = np.random.default_rng(seed)
    out = {}
    for k, v in params.items():
        if k == "a/w":
            out[k] = v
            continue
        scale = 1e-6 if k == "d/w" else 1e-2
        mask = rng.random(v.shape) < 0.1
        noise = rng.standard_normal(v.shape) * scale
        out[k] = (v + np.where(mask, noise, 0.0)).astype(np.float32)
    return out


def _stream_chain(root, **kw):
    """A depth-2 chunked chain; returns the store, its refs, the tip's
    params and a child of the tip not yet committed."""
    store = ArtifactStore(root=str(root), **dict(CHUNK_KW, **kw))
    rng = np.random.default_rng(0)
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in STREAM_LEAVES.items()}
    refs = [store.commit_artifact("c0", ModelArtifact(_stream_graph(),
                                                      params))]
    for k in (1, 2):
        params = _stream_finetune(params, k)
        refs.append(store.commit_artifact(
            f"c{k}", ModelArtifact(_stream_graph(), params),
            parent_ref=refs[-1]))
    return store, refs, _stream_finetune(params, 3)


STREAM_COUNTERS = ("chunks_written", "chunks_deduped", "chunk_delta_blobs",
                   "chunk_passthrough")


@pytest.mark.parametrize("window", ["below_one_chunk", "default"])
@pytest.mark.parametrize("io_workers", [1, 4, 16])
def test_chunk_stream_commit_is_byte_identical(tmp_path, io_workers, window):
    """The stream changes when chunks run, never what a commit stores: the
    same manifest ref, CAS objects, refcounts and chunk counters as a serial
    commit, whatever the workers and the window (16 workers, more than the
    cores, with threads switching every 10 us)."""
    def child_commit(root, **kw):
        store, refs, child = _stream_chain(root, **kw)
        store.reset_io_stats()
        ref = store.commit_artifact("c3", ModelArtifact(_stream_graph(),
                                                        child),
                                    parent_ref=refs[-1])
        return store, ref

    want_store, want = child_commit(tmp_path / "serial", io_workers=1)
    kw = ({"chunk_window_bytes": CHUNK_KW["chunk_min"]}
          if window == "below_one_chunk" else {})
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        store, got = child_commit(tmp_path / "stream",
                                  io_workers=io_workers, **kw)
    finally:
        sys.setswitchinterval(interval)
    assert got == want
    assert set(store.cas.keys()) == set(want_store.cas.keys())
    assert store.cas.refcounts == want_store.cas.refcounts
    counts = {k: store.io_stats[k] for k in STREAM_COUNTERS}
    assert counts == {k: want_store.io_stats[k] for k in STREAM_COUNTERS}
    assert all(counts.values()), counts   # every kind of chunk occurs
    entries = store.get_manifest(got)["params"]
    assert [k for k in entries if entries[k]["kind"] == "chunked"] \
        == CHUNKED
    for k in CHUNKED:
        assert entries[k]["hash"] == tensor_hash(
            store.materialize_param(got, k))


@pytest.mark.parametrize("io_workers", [2, 16])
def test_checkout_spreads_a_leafs_chunks_over_the_pool(tmp_path, io_workers):
    """``materialize_artifact`` hands each leaf to one pool worker, which
    spreads the leaf's chunks over the pool, running itself those no other
    worker has started: with fewer workers than leaves, or more workers
    than cores, and threads switching every 10 us, the checkout ends and
    every leaf is bit for bit the serial one; with workers to spare, a
    leaf's chunks ran on more than one thread."""
    store, refs, child = _stream_chain(tmp_path / "repo")
    ref = store.commit_artifact("c3", ModelArtifact(_stream_graph(), child),
                                parent_ref=refs[-1])
    serial = ArtifactStore(root=str(tmp_path / "repo"), io_workers=1,
                           **CHUNK_KW)
    want = {k: serial.materialize_param(ref, k) for k in CHUNKED}
    got = {}

    def checkout():
        wide = ArtifactStore(root=str(tmp_path / "repo"),
                             io_workers=io_workers, **CHUNK_KW)
        with tracing():
            got.update(wide.materialize_artifact(ref).params)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    reset_trace()
    try:
        t = threading.Thread(target=checkout)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive(), "checkout did not finish"
        evs = [e for e in export_chrome_trace()["traceEvents"]
               if e.get("ph") == "X"]
    finally:
        sys.setswitchinterval(interval)
        reset_trace()
    for k in CHUNKED:
        assert got[k].tobytes() == want[k].tobytes(), k
    params = {e["args"]["span_id"]: e["args"]["key"] for e in evs
              if e["name"] == "checkout.param"}
    threads = {}
    for e in evs:
        if e["name"] == "chunk.read" and e["args"]["parent_id"] in params:
            threads.setdefault(params[e["args"]["parent_id"]],
                               set()).add(e["tid"])
    if io_workers > len(CHUNKED):
        assert max(len(t) for t in threads.values()) > 1, threads


@pytest.mark.parametrize("batched", [False, True])
def test_checkout_of_a_missing_chunk_raises_and_pool_survives(tmp_path,
                                                              batched):
    """A chunk object gone from the CAS fails the leaf's checkout on the
    calling thread, whether the caller is off the pool (a direct
    ``materialize_param``) or a pool worker (``materialize_artifact``);
    the leaf's other chunks are cancelled or waited for, and the same
    store then checks out an intact leaf."""
    store = ArtifactStore(root=str(tmp_path), pack_threshold=1024,
                          io_workers=4, **CHUNK_KW)
    art, _ = big_artifact()
    _, w2 = big_artifact(seed=5)
    g2 = LayerGraph.chain([LayerNode("b2", "linear",
                                     params={"w": (w2.shape, "float32")})])
    bad = store.commit_artifact("m", art)
    good = store.commit_artifact("g", ModelArtifact(g2, {"b2/w": w2}))
    chunks_ = store.get_manifest(bad)["params"]["big/w"]["chunks"]
    assert len(chunks_) > 2
    os.remove(os.path.join(str(tmp_path), "objects",
                           chunks_[len(chunks_) // 2]["c"]))
    store = ArtifactStore(root=str(tmp_path), pack_threshold=1024,
                          io_workers=4, **CHUNK_KW)
    with pytest.raises(KeyError):
        if batched:
            store.materialize_artifact(bad)
        else:
            store.materialize_param(bad, "big/w")
    assert store.materialize_param(good, "b2/w").tobytes() == w2.tobytes()


class _Ledger:
    """Chunk bytes read from the sources and not yet consumed, the consume
    marker being the chunk's ``put_bytes`` under its content key."""

    def __init__(self):
        self.lock = threading.Lock()
        self.open = {}
        self.peak_bytes = self.peak_chunks = 0

    def read(self, data):
        with self.lock:
            self.open["c_" + bytes_hash(data)] = len(data)
            self.peak_bytes = max(self.peak_bytes, sum(self.open.values()))
            self.peak_chunks = max(self.peak_chunks, len(self.open))

    def consumed(self, key):
        with self.lock:
            self.open.pop(key, None)


class _TrackedSource(chunklib.ArraySource):
    def __init__(self, arr, ledger):
        super().__init__(arr)
        self._ledger = ledger

    def read(self, offset, size):
        data = super().read(offset, size)
        self._ledger.read(bytes(data))
        return data


@pytest.mark.parametrize("window", [CHUNK_KW["chunk_min"],
                                    12 * CHUNK_KW["chunk_max"]])
def test_chunk_stream_stays_within_window(tmp_path, monkeypatch, window):
    """Chunks in flight never hold more than the window (4 x their bytes
    each); one costlier than the window runs alone. The counters land in
    ``io_stats`` and on the commit's one ``commit.chunk_stream`` span."""
    store = ArtifactStore(root=str(tmp_path), io_workers=4,
                          chunk_window_bytes=window, chunk_mode="fixed",
                          **CHUNK_KW)
    ledger = _Ledger()
    put_bytes = store.cas.put_bytes

    def marked_put(data, key=None, **kw):
        if key is not None:
            ledger.consumed(key)
        return put_bytes(data, key=key, **kw)

    monkeypatch.setattr(store.cas, "put_bytes", marked_put)
    rng = np.random.default_rng(0)
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in STREAM_LEAVES.items()}
    params.update({k: _TrackedSource(params[k], ledger) for k in CHUNKED})
    reset_trace()
    try:
        with tracing():
            ref = store.commit_artifact(
                "m", ModelArtifact(_stream_graph(), params))
        streams = [e for e in export_chrome_trace()["traceEvents"]
                   if e.get("name") == "commit.chunk_stream"]
    finally:
        reset_trace()
    entries = store.get_manifest(ref)["params"]
    n_chunks = sum(len(entries[k]["chunks"]) for k in CHUNKED)
    assert ledger.open == {} and ledger.peak_chunks > 0
    assert 4 * ledger.peak_bytes <= max(window, 4 * CHUNK_KW["chunk_max"])
    if window < 4 * CHUNK_KW["chunk_min"]:
        assert ledger.peak_chunks == 1      # every chunk admitted alone
    else:
        assert 4 * ledger.peak_bytes <= window
        assert ledger.peak_chunks > 1
    stalls = store.io_stats["chunk_window_stalls"]
    assert stalls > 0
    assert len(streams) == 1
    args = streams[0]["args"]
    assert (args["leaves"], args["chunks"]) == (len(CHUNKED), n_chunks)
    assert args["chunk_window_stalls"] == stalls
    assert args["chunk_head_waits"] == store.io_stats["chunk_head_waits"]


class _FailingSource(chunklib.ArraySource):
    """Raises on any read past its first chunk."""

    def read(self, offset, size):
        if offset > 0:
            raise OSError("chunk source read failed")
        return super().read(offset, size)


@pytest.mark.parametrize("io_workers", [1, 4])
def test_chunk_stream_failure_publishes_nothing(tmp_path, io_workers):
    """A read failing in the second leaf fails the commit: no manifest, the
    references its first (frozen, deduped) leaf took are dropped so fsck
    stays clean, and the same store's pool commits next."""
    store, refs, child = _stream_chain(tmp_path, io_workers=io_workers)
    manifests = {k for k in store.cas.keys() if k.startswith("m_")}
    bad = dict(child, **{"b/w": _FailingSource(child["b/w"])})
    with pytest.raises(OSError, match="chunk source read failed"):
        store.commit_artifact("bad", ModelArtifact(_stream_graph(), bad),
                              parent_ref=refs[-1])
    assert {k for k in store.cas.keys() if k.startswith("m_")} == manifests
    report = store.fsck(refs)
    assert report["ok"], report
    ref = store.commit_artifact("c3", ModelArtifact(_stream_graph(), child),
                                parent_ref=refs[-1])
    for k in CHUNKED:
        assert store.get_manifest(ref)["params"][k]["hash"] == tensor_hash(
            store.materialize_param(ref, k))
    report = store.fsck(refs + [ref])
    assert report["ok"], report


# ---------------------------------------------------------------------------
# fsck pinpoints chunk damage
# ---------------------------------------------------------------------------

def _loose_chunk_store(tmp_path):
    """Chunk objects land loose (tiny pack threshold) so tests can corrupt
    a single chunk file on disk."""
    return ArtifactStore(root=str(tmp_path), pack_threshold=1024, **CHUNK_KW)


def test_fsck_pinpoints_corrupt_chunk(tmp_path):
    store = _loose_chunk_store(tmp_path)
    art, _ = big_artifact()
    ref = store.commit_artifact("m", art)
    e = store.get_manifest(ref)["params"]["big/w"]
    victim = next(it["c"] for it in e["chunks"] if "c" in it)
    vpath = os.path.join(str(tmp_path), "objects", victim)
    data = bytearray(open(vpath, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(vpath, "wb").write(bytes(data))

    report = store.fsck([ref])
    assert victim in report["corrupt"]
    damage = [d for d in report["chunk_damage"] if d["object"] == victim]
    assert damage and damage[0]["ref"] == ref
    assert damage[0]["param"] == "big/w"
    assert damage[0]["problem"] == "corrupt"
    # the hit names exactly the bad chunk's index, not the whole tensor
    idx = damage[0]["chunk"]
    assert e["chunks"][idx]["c"] == victim
    healthy = [d for d in report["chunk_damage"] if d["object"] != victim]
    assert not healthy


def test_fsck_detects_dangling_chunk_ref(tmp_path):
    store = _loose_chunk_store(tmp_path)
    art, _ = big_artifact(seed=3)
    ref = store.commit_artifact("m", art)
    e = store.get_manifest(ref)["params"]["big/w"]
    victim = next(it["c"] for it in e["chunks"] if "c" in it)
    os.remove(os.path.join(str(tmp_path), "objects", victim))

    report = store.fsck([ref])
    assert not report["ok"]
    assert victim in report["missing_objects"]
    damage = [d for d in report["chunk_damage"] if d["object"] == victim]
    assert damage and damage[0]["problem"] == "missing"


def test_fsck_clean_chunked_repo_ok(tmp_path):
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, w = big_artifact()
    r1 = store.commit_artifact("m", art)
    r2 = store.commit_artifact(
        "m", ModelArtifact(art.graph, {"big/w": edit(w)}), parent_ref=r1)
    report = store.fsck([r1, r2])
    assert report["ok"] and not report["chunk_damage"]
    assert not report["refcount_drift"]


# ---------------------------------------------------------------------------
# mmap pool eviction leaves outstanding views valid
# ---------------------------------------------------------------------------

def test_mmap_pool_eviction_keeps_views_alive(tmp_path):
    cas = CAS(str(tmp_path), pack_threshold=10 ** 9, mmap_pool_max=2)
    arrays = {f"t{i}": np.full(4096, i, dtype=np.float32) for i in range(8)}
    keys = {name: cas.put_tensor(arr) for name, arr in arrays.items()}
    # hold zero-copy views of every object while the pool (capacity 2)
    # evicts the earlier maps many times over
    views = {name: cas.get_tensor(keys[name]) for name in arrays}
    raw = {name: cas.get_view(keys[name]) for name in arrays}
    assert len(cas._mmap_pool) <= 2
    for name, arr in arrays.items():
        np.testing.assert_array_equal(views[name], arr)   # evicted map alive
        # the raw view is the stored npy payload; it must still read
        # correctly even though its backing map was evicted from the pool
        assert bytes(raw[name]) == cas.get_bytes_nomap(keys[name])
        assert not views[name].flags.writeable


def test_small_mmap_pool_serves_chunked_checkout(tmp_path):
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    store.cas._mmap_pool_max = 1
    art, w = big_artifact()
    ref = store.commit_artifact("m", art)
    np.testing.assert_array_equal(store.materialize_param(ref, "big/w"), w)


# ---------------------------------------------------------------------------
# sync: chunk-granular negotiation, ranged fetch, shard pull
# ---------------------------------------------------------------------------

def _lineage(tmp_path, name, **kw):
    root = str(tmp_path / name)
    store = ArtifactStore(root=root, **kw)
    return LineageGraph(path=root, store=store), store


def test_pull_moves_only_edited_chunks(tmp_path):
    from repro.remote.sync import pull, push
    g1, store = _lineage(tmp_path, "src", **CHUNK_KW)
    art, w = big_artifact()
    g1.add_node(art, "m")
    remote = LocalTransport(str(tmp_path / "remote"))
    push(g1, remote)
    g2, _ = _lineage(tmp_path, "dst", **CHUNK_KW)
    pull(g2, remote)
    baseline = push(g1, remote).objects_transferred
    assert baseline == 0                       # fully synced

    g1.add_node(ModelArtifact(art.graph, {"big/w": edit(w)}), "m2")
    g1.add_version_edge("m", "m2")
    rep = push(g1, remote)
    e = store.get_manifest(g1.nodes["m2"].artifact_ref)["params"]["big/w"]
    total_chunks = len(e["chunks"])
    # only the new manifest + the few changed chunk objects moved
    assert 0 < rep.objects_transferred < total_chunks
    rep2 = pull(g2, remote)
    assert 0 < rep2.objects_transferred < total_chunks
    got = np.asarray(g2.store.load_artifact(
        g2.nodes["m2"].artifact_ref).params["big/w"])
    np.testing.assert_array_equal(
        got, np.asarray(store.load_artifact(
            g1.nodes["m2"].artifact_ref).params["big/w"]))


def test_fetch_objects_local_transport(tmp_path):
    g1, store = _lineage(tmp_path, "src", **CHUNK_KW)
    art, _ = big_artifact()
    g1.add_node(art, "m")
    t = LocalTransport(str(store.cas.root))
    ref = g1.nodes["m"].artifact_ref
    e = store.get_manifest(ref)["params"]["big/w"]
    keys = [it["c"] for it in e["chunks"] if "c" in it][:4] + [ref]
    got = fetch_objects(t, keys)
    assert set(got) == set(keys)
    for k in keys:
        assert got[k] == store.cas.get_bytes(k)
    assert t.object_sizes(keys) == {k: len(got[k]) for k in keys}
    assert t.object_sizes(["missing_key"]) == {}


def test_fetch_param_shard_local(tmp_path):
    g1, store = _lineage(tmp_path, "src", chunk_shards=4, **CHUNK_KW)
    art, w = big_artifact()
    g1.add_node(art, "m")
    ref = g1.nodes["m"].artifact_ref
    t = LocalTransport(str(store.cas.root))
    raw = w.tobytes()
    row_bytes = w.shape[1] * 4
    consumer = ArtifactStore(root=str(tmp_path / "host2"))
    got = fetch_param_shard(consumer, t, ref, "big/w", 2, 4)
    rows = w.shape[0]
    start = (2 * rows) // 4 * row_bytes
    end = (3 * rows) // 4 * row_bytes
    assert got == raw[start:end]
    # the consumer imported strictly fewer chunk objects than exist
    e = json.loads(consumer.cas.get_bytes(ref))["params"]["big/w"]
    total_c = sum(1 for it in e["chunks"] if "c" in it)
    held = sum(1 for it in e["chunks"]
               if "c" in it and consumer.cas.has(it["c"]))
    assert 0 < held < total_c
    with pytest.raises(ValueError):
        fetch_param_shard(consumer, t, ref, "big/w", 4, 4)


def test_shard_grid_respects_mesh_cuts(tmp_path):
    """No chunk straddles a shard boundary when chunk_shards is set."""
    store = ArtifactStore(root=str(tmp_path), chunk_shards=4, **CHUNK_KW)
    art, w = big_artifact()
    ref = store.commit_artifact("m", art)
    e = store.get_manifest(ref)["params"]["big/w"]
    cuts = set(np.cumsum([int(it["n"]) for it in e["chunks"]]).tolist())
    from repro.dist.sharding import shard_cuts
    expected = shard_cuts("big/w", w.shape, 4, 4)
    assert expected and set(expected) <= cuts


def test_http_parallel_ranged_read_matches_single_stream(tmp_path):
    from repro.hub import HubApp, start_in_thread
    from repro.remote.http import HttpTransport
    app = HubApp(str(tmp_path / "hub"))
    payload = np.random.default_rng(0).bytes(3 * 2 ** 20)
    key = app.store.cas.put_bytes(payload)
    server, _ = start_in_thread(app)
    try:
        t = HttpTransport(server.url, retries=1, backoff=0.01)
        sizes = t.object_sizes([key, "nope"])
        assert sizes == {key: len(payload)}
        whole = t.read_object_range(key, 0, len(payload))
        par = t.read_object_parallel(key, len(payload),
                                     part_bytes=256 * 1024, workers=4)
        assert par == whole == payload
        # tiny objects short-circuit to one request
        assert t.read_object_parallel(key, len(payload),
                                      part_bytes=len(payload) + 1) == payload
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# lossless chunk hops: non-float32 leaves against their parent's truth
# ---------------------------------------------------------------------------

def _ulp_finetune(w, seed, density=0.1):
    """G2-style: a ``density`` share of the elements moves by a few ulps."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with NumPy)
    rng = np.random.default_rng(seed)
    bits = w.view(np.uint16).copy()
    mask = rng.random(bits.shape) < density
    bits[mask] += rng.integers(1, 3, int(mask.sum())).astype(np.uint16)
    return bits.view(w.dtype)


def _specials(w, seed):
    """Sparse bit patterns no arithmetic produces from normal values:
    NaNs with payloads, both infinities, -0 and subnormals."""
    rng = np.random.default_rng(seed)
    bits = w.view(np.uint16).copy().reshape(-1)
    pos = rng.choice(bits.size, 600, replace=False)
    exp = {"bfloat16": 0x7F80, "float16": 0x7C00}[str(w.dtype)]
    patterns = np.array([exp | 1, exp | 0x8003, exp, 0x8000 | exp, 0x8000,
                         0x0001, 0x8002, exp - 1], dtype=np.uint16)
    bits[pos] = patterns[np.arange(pos.size) % patterns.size]
    return bits.view(w.dtype).reshape(w.shape)


def _item_kind(item):
    return "x" if item.get("x") else next(k for k in ("c", "b", "p")
                                          if k in item)


def _assert_checks_out(root, ref, want, **kw):
    """A fresh store's every checkout path gives ``want`` bit for bit, and
    the entry's hash is ``tensor_hash`` of it."""
    store = ArtifactStore(root=str(root), **dict(CHUNK_KW, **kw))
    e = store.get_manifest(ref)["params"]["big/w"]
    assert e["hash"] == tensor_hash(want)
    raw = want.tobytes()
    got = store.materialize_param(ref, "big/w")
    assert got.dtype == want.dtype and got.tobytes() == raw
    assert b"".join(bytes(d) for _, d in store.stream_param(ref, "big/w")) \
        == raw
    assert store.materialize_param_range(ref, "big/w", 1000, 90_001) == \
        raw[1000:90_001]


@pytest.mark.parametrize("change", ["finetune", "specials"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_lossless_chunk_hop_round_trips(tmp_path, dtype, change):
    """A 16-bit leaf committed against its parent stores each changed
    chunk as a lossless bit-pattern hop (``x``), and every checkout gives
    the child's own bits, whatever they encode."""
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, w = big_artifact(dtype=dtype)
    r1 = store.commit_artifact("m", art)
    child = (_ulp_finetune(w, 1) if change == "finetune"
             else _specials(w, 1))
    r2 = store.commit_artifact("m", ModelArtifact(art.graph,
                                                  {"big/w": child}),
                               parent_ref=r1)
    e = store.get_manifest(r2)["params"]["big/w"]
    assert e["parent_ref"] == r1
    assert {_item_kind(it) for it in e["chunks"]} == {"x"}
    for it in e["chunks"]:
        assert (it["q"], it["k"], it["x"]) == ("uint16", "sparse", 1)
    assert store.io_stats["chunk_xdelta_blobs"] == len(e["chunks"])
    assert 0 < store.io_stats["chunk_xdelta_bytes"] < 0.5 * child.nbytes
    assert store.io_stats["chunk_delta_blobs"] == 0
    _assert_checks_out(tmp_path, r2, child)


def test_lossless_chunk_hop_chain_and_depth_reset(tmp_path):
    """Three hops decode exactly, each from a fresh store, and the chain
    depth knob resets the fourth commit to raw chunks."""
    store = ArtifactStore(root=str(tmp_path), max_chain_depth=3, **CHUNK_KW)
    art, w = big_artifact(dtype="bfloat16")
    refs, values = [store.commit_artifact("m", art)], [w]
    for k in range(1, 5):
        values.append(_ulp_finetune(values[-1], k))
        refs.append(store.commit_artifact(
            "m", ModelArtifact(art.graph, {"big/w": values[-1]}),
            parent_ref=refs[-1]))
    for k in range(1, 4):
        e = store.get_manifest(refs[k])["params"]["big/w"]
        assert {_item_kind(it) for it in e["chunks"]} == {"x"}
        assert store.get_manifest(refs[k])["depth"] == k
        _assert_checks_out(tmp_path, refs[k], values[k], max_chain_depth=3)
    e = store.get_manifest(refs[4])["params"]["big/w"]
    assert {_item_kind(it) for it in e["chunks"]} == {"c"}
    assert "parent_ref" not in e and store.get_manifest(refs[4])["depth"] == 0
    _assert_checks_out(tmp_path, refs[4], values[4], max_chain_depth=3)


def test_lossless_chunk_hop_not_smaller_stays_raw(tmp_path):
    """Where the hop's blob is no smaller than the chunk (every element
    rewritten with incompressible bits), the chunk is stored raw, as a
    float32 chunk would be."""
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, w = big_artifact(dtype="bfloat16")

    def noise(seed):
        rng = np.random.default_rng(seed)
        return rng.integers(0, 2 ** 16, w.shape, dtype=np.uint16).view(
            w.dtype)

    r1 = store.commit_artifact("m", ModelArtifact(art.graph,
                                                  {"big/w": noise(4)}))
    fresh = noise(5)
    r2 = store.commit_artifact("m", ModelArtifact(art.graph,
                                                  {"big/w": fresh}),
                               parent_ref=r1)
    e = store.get_manifest(r2)["params"]["big/w"]
    assert {_item_kind(it) for it in e["chunks"]} == {"c"}
    assert store.io_stats["chunk_xdelta_blobs"] == 0
    _assert_checks_out(tmp_path, r2, fresh)


def test_lossless_chunk_hops_walk_gc_and_sync(tmp_path):
    """The hops' blobs are ``b`` objects to the manifest walk, GC and sync:
    listed, kept while the child lives, pushed and pulled into a fresh
    store that checks the child out bit for bit; gone once released."""
    from repro.remote.sync import pull, push
    from repro.store.manifest_walk import parse_manifest
    g1, store = _lineage(tmp_path, "src", **CHUNK_KW)
    art, w = big_artifact(dtype="bfloat16")
    g1.add_node(art, "m")
    child = _ulp_finetune(w, 2)
    g1.add_node(ModelArtifact(art.graph, {"big/w": child}), "m2")
    g1.add_version_edge("m", "m2")
    ref = g1.nodes["m2"].artifact_ref
    blobs = [it["b"] for it in
             store.get_manifest(ref)["params"]["big/w"]["chunks"]
             if it.get("x")]
    assert blobs
    assert set(blobs) <= set(parse_manifest(store.cas.get_bytes(ref))
                             .objects)
    store.cas.gc()
    assert all(store.cas.has(b) for b in blobs)
    report = store.fsck([g1.nodes["m"].artifact_ref, ref])
    assert report["ok"], report
    remote = LocalTransport(str(tmp_path / "remote"))
    push(g1, remote)
    g2, dst = _lineage(tmp_path, "dst", **CHUNK_KW)
    pull(g2, remote)
    assert all(dst.cas.has(b) for b in blobs)
    got = np.asarray(dst.load_artifact(g2.nodes["m2"].artifact_ref)
                     .params["big/w"])
    assert got.tobytes() == child.tobytes()
    store.release(ref)
    store.cas.gc()
    assert not any(store.cas.has(b) for b in blobs)


def test_float32_chunk_items_keep_their_kinds(tmp_path):
    """A float32 lineage commit stores quantized hops, raw chunks and
    pass-throughs as before: no item carries the lossless marker."""
    store, refs, child = _stream_chain(tmp_path)
    store.reset_io_stats()
    ref = store.commit_artifact("c3", ModelArtifact(_stream_graph(), child),
                                parent_ref=refs[-1])
    shapes = {tuple(sorted(it))
              for r in refs + [ref]
              for k in CHUNKED
              for it in store.get_manifest(r)["params"][k]["chunks"]}
    assert shapes <= {("c", "n"), ("b", "n", "q"), ("b", "k", "n", "q"),
                      ("n", "p")}
    assert {("c", "n"), ("n", "p")} < shapes
    assert store.io_stats["chunk_delta_blobs"] > 0
    assert store.io_stats["chunk_xdelta_blobs"] == 0


def test_exact_tier_keeps_bf16_changed_chunks_raw(tmp_path):
    """The exact checkpoint tier's chunked leaves take no hop: a changed
    bf16 chunk is stored raw, so the entry's truth is the live value."""
    store = ArtifactStore(root=str(tmp_path), **CHUNK_KW)
    art, w = big_artifact(dtype="bfloat16")
    r0 = store.commit_step("s0", {"big/w": w},
                           graph_json=art.graph.to_json())
    child = _ulp_finetune(w, 3)
    r1 = store.commit_step("s1", {"big/w": child}, parent_ref=r0,
                           tier="exact")
    e = store.get_manifest(r1)["params"]["big/w"]
    assert e["kind"] == "chunked"
    assert {_item_kind(it) for it in e["chunks"]} == {"c"}
    assert store.io_stats["chunk_xdelta_blobs"] == 0
    assert store.materialize_param(r1, "big/w").tobytes() == child.tobytes()


# ---------------------------------------------------------------------------
# device -> host copy through a uint32 view (ops.host_copy)
# ---------------------------------------------------------------------------

def _special_bits(dtype, size, seed):
    """``size`` elements of ``dtype``: normal values with NaNs (payloads
    too), both infinities, -0 and subnormals of either sign mixed in."""
    import ml_dtypes
    dt = np.dtype(dtype)
    width = 8 * dt.itemsize
    uint = np.dtype(f"uint{width}")
    rng = np.random.default_rng(seed)
    bits = rng.standard_normal(size).astype(dt).view(uint)
    inf = int(np.array(np.inf, dt).view(uint))
    sign, quiet = 1 << (width - 1), 1 << (ml_dtypes.finfo(dt).nmant - 1)
    patterns = np.array([inf | 1, inf | quiet, inf | quiet | 3, inf,
                         sign | inf, sign, 1, sign | 1, quiet - 1,
                         sign | (2 * quiet - 1)], dtype=uint)
    pos = rng.choice(size, min(size, 4 * patterns.size), replace=False)
    bits[pos] = patterns[np.arange(pos.size) % patterns.size]
    return bits.view(dt)


VIEW_CASES = ([(dt, shape, "normal")
               for dt in ("bfloat16", "float16", "float32", "int8")
               for shape in ((4096,), (48, 88), (3, 20, 36))]
              + [("bfloat16", (5, 7), "normal"),      # odd count: no words
                 ("bfloat16", (40, 64), "specials"),
                 ("float16", (2, 30, 32), "specials"),
                 ("float32", (1000,), "specials")])


@pytest.mark.parametrize("dtype,shape,content", VIEW_CASES)
def test_host_copy_u32_view_is_bit_identical(monkeypatch, dtype, shape,
                                             content):
    """The uint32 view copies a leaf's bytes exactly as ``np.asarray`` does,
    whatever they encode; a leaf whose elements do not fill whole words
    falls back to ``np.asarray``."""
    import jax.numpy as jnp
    from repro.kernels import ops

    size = int(np.prod(shape))
    if content == "specials":
        want = _special_bits(dtype, size, seed=len(shape)).reshape(shape)
    else:
        rng = np.random.default_rng(size)
        want = (rng.standard_normal(shape) * 50).astype(dtype)
    x = jnp.asarray(want)
    raw = np.asarray(x).tobytes()
    assert raw == want.tobytes()
    monkeypatch.setattr(ops, "_tiled", lambda a: True)  # as on a TPU
    got, view = ops.host_copy(x)
    packs = size % (4 // np.dtype(dtype).itemsize) == 0
    assert view == ("u32" if packs else "asis")
    assert (ops.u32_view(x) is None) == (not packs)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == raw


VIEW_LEAVES = {"a/w": ((264, 300), "bfloat16"), "b/w": ((200, 330), "float16"),
               "c/w": ((130, 261), "float32")}


def _view_artifact(seed):
    g = LayerGraph.chain([LayerNode(k.split("/")[0], "linear",
                                    params={"w": spec})
                          for k, spec in VIEW_LEAVES.items()])
    rng = np.random.default_rng(seed)
    return g, {k: rng.standard_normal(s).astype(dt)
               for k, (s, dt) in VIEW_LEAVES.items()}


def test_device_commit_through_u32_view_matches_numpy_commit(tmp_path,
                                                             monkeypatch):
    """A derivative committed from device arrays through the uint32 view
    and the same derivative committed from NumPy arrays store the same
    manifest and the same chunk objects; only the device commit counts
    viewed leaves, and a later commit of the same shapes, in a fresh store,
    traces the view no further time."""
    import jax.numpy as jnp
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_tiled", lambda a: True)  # as on a TPU
    graph, base = _view_artifact(0)
    _, noise = _view_artifact(1)
    child = {k: (v + noise[k] * 1e-2).astype(v.dtype)
             for k, v in base.items()}

    def commit(root, params, before=None):
        store = ArtifactStore(root=str(root), **CHUNK_KW)
        parent = before or store.commit_artifact("base",
                                                 ModelArtifact(graph, base))
        store = ArtifactStore(root=str(root), **CHUNK_KW)
        ref = store.commit_artifact("child", ModelArtifact(graph, params),
                                    parent_ref=parent)
        return store, ref, parent

    traces = ops.VIEW_TRACES["traces"]
    dev_store, dev_ref, parent = commit(
        tmp_path / "dev", {k: jnp.asarray(v) for k, v in child.items()})
    assert 0 < ops.VIEW_TRACES["traces"] - traces <= len(VIEW_LEAVES)
    np_store, np_ref, _ = commit(tmp_path / "np", child)
    assert dev_ref == np_ref
    assert set(dev_store.cas.keys()) == set(np_store.cas.keys())
    entries = dev_store.get_manifest(dev_ref)["params"]
    assert all(entries[k]["kind"] == "chunked" for k in VIEW_LEAVES)
    assert dev_store.io_stats["d2h_view_leaves"] == len(VIEW_LEAVES)
    assert dev_store.io_stats["d2h_view_bytes"] == sum(
        v.nbytes for v in child.values())
    assert np_store.io_stats["d2h_view_leaves"] == 0
    assert np_store.io_stats["d2h_view_bytes"] == 0

    traces = ops.VIEW_TRACES["traces"]
    _, noise = _view_artifact(2)
    again = {k: jnp.asarray((v + noise[k] * 1e-2).astype(v.dtype))
             for k, v in base.items()}
    store, ref, _ = commit(tmp_path / "dev", again, before=parent)
    assert ops.VIEW_TRACES["traces"] == traces
    assert store.io_stats["d2h_view_leaves"] == len(VIEW_LEAVES)
    for k in ("a/w", "b/w"):   # 16-bit hops are lossless
        assert store.materialize_param(ref, k).tobytes() == \
            np.asarray(again[k]).tobytes()
