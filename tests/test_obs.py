"""Observability layer (DESIGN.md §14): metrics registry, trace spans,
Prometheus exposition on both daemons, retry/watcher visibility."""

import contextlib
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.cli import main as cli
from repro.core import LineageGraph, ModelArtifact
from repro.hub import HubApp
from repro.hub import start_in_thread as hub_start
from repro.obs import (REGISTRY, Histogram, Registry, propagate, reset_trace,
                       span, tracing)
from repro.obs import export_chrome_trace, is_enabled
from repro.remote import (HttpTransport, LocalTransport, RemoteState, push)
from repro.remote.http import HubUnavailable, endpoint_family
from repro.serve import (LineageWatcher, LocalLineageSource, ModelPool,
                         Router, ServeApp)
from repro.serve import start_in_thread as serve_start
from repro.store import ArtifactStore

from helpers import finetune_like, make_chain_model, perturb


@pytest.fixture(autouse=True)
def _clean_trace():
    """Tracing state is process-global; leave it as we found it (off)."""
    reset_trace()
    yield
    assert not is_enabled()
    reset_trace()


def _repo(path):
    path = str(path)
    return LineageGraph(path=path, store=ArtifactStore(root=path))


def _seed(g):
    base = make_chain_model(seed=0, d=32)
    g.add_node(base, "m@v1")
    g.add_edge("m@v1", "m@v2")
    g.add_node(finetune_like(base, seed=1), "m@v2")
    return base


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_identity_and_kind_guard():
    r = Registry()
    c = r.counter("t_reqs", help="h", route="/a")
    assert r.counter("t_reqs", route="/a") is c      # same child handle
    assert r.counter("t_reqs", route="/b") is not c  # new label set
    c.inc()
    c.inc(4)
    assert c.get() == 5
    g = r.gauge("t_depth")
    g.inc(3)
    g.dec()
    assert g.get() == 2
    with pytest.raises(ValueError):
        r.gauge("t_reqs")  # family kind is fixed at first registration


def test_counter_increments_are_thread_safe():
    r = Registry()
    c = r.counter("t_par")

    def worker():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.get() == 80_000


def test_histogram_quantile_matches_numpy_within_bucket_width():
    r = Registry()
    h = r.histogram("t_lat", buckets=[b / 1000 for b in range(1, 101)])
    rng = np.random.default_rng(7)
    obs = rng.uniform(0.001, 0.1, size=5000)
    for v in obs:
        h.observe(float(v))
    for q in (0.5, 0.9, 0.99):
        est = h.quantile(q)
        exact = float(np.percentile(obs, q * 100))
        # linear interpolation inside a 1ms bucket: within one bucket width
        assert abs(est - exact) <= 0.001 + 1e-9, (q, est, exact)
    assert r.histogram("t_lat").count == 5000


def test_histogram_edge_cases():
    r = Registry()
    h = r.histogram("t_edge", buckets=[0.1, 1.0])
    assert h.quantile(0.5) is None  # empty
    h.observe(50.0)                 # beyond the last bound -> +Inf bucket
    assert h.quantile(0.99) == 1.0  # clamps to last finite bound
    text = r.render_prometheus()
    assert 't_edge_bucket{le="+Inf"} 1' in text
    assert 't_edge_bucket{le="1"} 0' in text


def _parse_prometheus(text):
    """Minimal exposition-format parser: {(name, labels_str): value}."""
    samples, types = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(None, 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        metric, _, value = line.rpartition(" ")
        assert metric and value, f"unparseable line {line!r}"
        float(value)  # must be a number
        samples[metric] = float(value)
    return samples, types


def test_prometheus_rendering_is_parseable_and_escaped():
    r = Registry()
    r.counter("t_esc", help="has labels", path='a"b\\c\nd').inc(2)
    h = r.histogram("t_hist", buckets=[0.5])
    h.observe(0.1)
    h.observe(9.0)
    samples, types = _parse_prometheus(r.render_prometheus())
    assert types == {"t_esc": "counter", "t_hist": "histogram"}
    assert samples['t_esc{path="a\\"b\\\\c\\nd"}'] == 2
    assert samples['t_hist_bucket{le="0.5"}'] == 1
    assert samples['t_hist_bucket{le="+Inf"}'] == 2  # cumulative
    assert samples["t_hist_count"] == 2


def test_metric_group_dict_compat():
    r = Registry()
    g = r.group("t_grp", keys=("a", "b"), instance="x")
    g["a"] += 3          # legacy increment pattern
    g.inc("b", 2)
    g["dynamic"] = 7     # unknown keys materialize on first write
    assert g["a"] == 3 and g.get("b") == 2 and g.get("nope", -1) == -1
    assert set(g) == {"a", "b", "dynamic"} and len(g) == 3
    assert dict(g) == {"a": 3, "b": 2, "dynamic": 7}
    assert {**g, "extra": 1}["a"] == 3
    assert g == {"a": 3, "b": 2, "dynamic": 7}
    assert 't_grp_a{instance="x"} 3' in r.render_prometheus()


def test_metric_group_reset_is_atomic_under_concurrent_increments():
    r = Registry()
    g = r.group("t_atomic", keys=("x", "y"))
    stop = threading.Event()
    torn = []

    def resetter():
        while not stop.is_set():
            snap = g.reset()
            # x and y are always incremented together under the group
            # lock via inc(); a reset can never observe one without the
            # other drifting by more than the in-flight pair
            if abs(snap["x"] - snap["y"]) > 1:
                torn.append(snap)

    t = threading.Thread(target=resetter)
    t.start()
    for _ in range(20_000):
        with g._lock:
            for k in ("x", "y"):
                g._metrics[k].value += 1
    stop.set()
    t.join()
    assert not torn


def test_store_reset_io_stats_snapshots_atomically(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    g = LineageGraph(path=str(tmp_path), store=store)
    _seed(g)
    store.materialize_artifact(g.nodes["m@v2"].artifact_ref)
    snap = store.io_stats.snapshot()
    assert snap["tensors_materialized"] > 0
    before = store.reset_io_stats()
    assert before["tensors_materialized"] == snap["tensors_materialized"]
    assert store.io_stats.snapshot()["tensors_materialized"] == 0
    # the registry sees the same (now reset) counters
    text = REGISTRY.render_prometheus()
    assert (f'mgit_store_tensors_materialized{{instance='
            f'"{store.io_stats.instance}"}} 0') in text


# ---------------------------------------------------------------------------
# Trace spans
# ---------------------------------------------------------------------------

def _span_events():
    return [e for e in export_chrome_trace()["traceEvents"]
            if e.get("ph") == "X"]


def test_disabled_tracing_records_nothing():
    with span("invisible", cat="test"):
        pass
    assert _span_events() == []
    fn = lambda: 1  # noqa: E731
    assert propagate(fn) is fn  # disabled: callable returned untouched


def test_span_tree_nests_and_propagates_across_threads():
    with tracing():
        with span("parent", cat="test"):
            with span("child", cat="test"):
                pass

            def task():
                with span("pooled", cat="test"):
                    return 1

            t = threading.Thread(target=propagate(task))
            t.start()
            t.join()
    evs = {e["name"]: e["args"] for e in _span_events()}
    assert evs["child"]["parent_id"] == evs["parent"]["span_id"]
    assert evs["pooled"]["parent_id"] == evs["parent"]["span_id"]


def test_span_records_error_and_trees_reconnect():
    with tracing():
        with pytest.raises(RuntimeError):
            with span("boom", cat="test"):
                raise RuntimeError("x")
    (ev,) = _span_events()
    assert ev["args"]["error"] == "RuntimeError"
    assert ev["dur"] >= 0


def test_traced_commit_is_one_connected_tree(tmp_path):
    store = ArtifactStore(root=str(tmp_path), io_workers=4)
    g = LineageGraph(path=str(tmp_path), store=store)
    base = make_chain_model(seed=0, d=32)
    with tracing():
        g.add_node(base, "m@v1")
        g.add_edge("m@v1", "m@v2")
        g.add_node(finetune_like(base, seed=1), "m@v2")
    evs = _span_events()
    by_id = {e["args"]["span_id"]: e for e in evs}
    roots = [e for e in evs if e["args"]["parent_id"] is None]
    assert {e["name"] for e in roots} == {"store.commit"}
    names = {e["name"] for e in evs}
    assert {"commit.delta", "commit.encode", "commit.hash",
            "commit.pack_fsync"} <= names
    # every worker-side span reaches a store.commit root via parent_id —
    # propagate() carried the submitting span into the pool threads
    for e in evs:
        cur = e
        while cur["args"]["parent_id"] is not None:
            cur = by_id[cur["args"]["parent_id"]]
        assert cur["name"] == "store.commit"


def test_traced_push_connects_transfer_chunks(tmp_path):
    g = _repo(tmp_path / "src")
    _seed(g)
    dst = str(tmp_path / "dst")
    with tracing():
        rep = push(g, LocalTransport(dst), state=RemoteState(g.path, "o"))
    assert rep.published
    evs = _span_events()
    by_id = {e["args"]["span_id"]: e for e in evs}
    names = {e["name"] for e in evs}
    assert {"sync.push", "sync.negotiate", "sync.transfer",
            "sync.publish", "journal.chunk"} <= names
    chunks = [e for e in evs if e["name"] == "journal.chunk"]
    assert chunks and all(
        by_id[c["args"]["parent_id"]]["name"] == "sync.transfer"
        for c in chunks)
    (root,) = [e for e in evs if e["args"]["parent_id"] is None]
    assert root["name"] == "sync.push"
    # LocalTransport has no retry_stats: report shows zeros, not crashes
    assert rep.transport_retries == 0 and rep.transport_retries_by_family == {}


def test_chrome_trace_has_thread_metadata():
    with tracing():
        with span("s", cat="test"):
            pass
    doc = export_chrome_trace()
    metas = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert any(m["name"] == "process_name" for m in metas)
    assert any(m["name"] == "thread_name" for m in metas)
    json.dumps(doc)  # exportable as-is


def test_disabled_span_enters_no_annotation(monkeypatch):
    from repro.obs import trace as obs_trace
    entered = []

    def annotation(name):
        entered.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(obs_trace._state, "annotation", annotation)
    with span("invisible", cat="test", n=1):
        pass
    assert span("a") is span("b")  # the one cached null span
    assert entered == [] and _span_events() == []
    with tracing():
        with span("seen", cat="test", n=1):
            pass
    assert entered == ["seen"]  # the name only, no arguments


def test_spans_have_profiler_twins_on_one_clock(tmp_path):
    """Every span is also a ``/host:`` event of the profiler's trace, on its
    real thread; put on the profiler's clock through the window's anchor (as
    the chip benchmark does), start and duration agree within 1 ms."""
    import sys
    import time
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from jax.profiler import ProfileData
    from chipbench import trace as bench_trace

    with bench_trace.Capture(str(tmp_path / "profile")) as cap:
        with span("clock.outer", cat="test"):
            time.sleep(0.003)
            with span("clock.inner", cat="test"):
                time.sleep(0.002)

            def task():
                with span("clock.pooled", cat="test"):
                    time.sleep(0.002)

            t = threading.Thread(target=propagate(task), name="clock-pool")
            t.start()
            t.join()
    evs = bench_trace.events(cap.xplane())
    (anchor,) = [e for e in evs if e["name"] == bench_trace.WINDOW_SPAN]
    host = [e for e in evs if e["plane"].startswith("/host:")]
    assert sorted(sp["name"] for sp in cap.spans) == [
        "clock.inner", "clock.outer", "clock.pooled"]
    for sp in cap.spans:
        start = anchor["start_ns"] + sp["start_ns"]
        (twin,) = [e for e in host if e["name"] == sp["name"]]
        assert abs(twin["start_ns"] - start) < 1_000_000, sp
        assert abs(twin["dur_ns"] - sp["dur_ns"]) < 1_000_000, sp
    # one profiler line per thread (lines are named by the OS thread name,
    # the same for both here, so they are told apart by position)
    line_of = {}
    for plane in ProfileData.from_file(cap.xplane()).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                line_of[ev.name] = (plane.name, i)
    assert line_of["clock.inner"] == line_of["clock.outer"]
    assert line_of["clock.pooled"] != line_of["clock.outer"]


def _roots(evs):
    by_id = {e["args"]["span_id"]: e for e in evs}
    out = {}
    for e in evs:
        cur = e
        while cur["args"]["parent_id"] is not None:
            cur = by_id[cur["args"]["parent_id"]]
        out[e["args"]["span_id"]] = cur["name"]
    return out


def _count(evs, name):
    return sum(e["name"] == name for e in evs)


def test_chunk_stage_spans_once_per_chunk_and_stage(tmp_path):
    """A float32 chunked tensor two delta hops from its base: a commit and a
    checkout open each ``chunk.*`` span once per chunk and stage (never per
    hop), all under ``store.commit`` / ``store.checkout``; a direct
    ``materialize_param`` fans its chunks out under its own span."""
    from test_chunks import CHUNK_KW, big_artifact

    store = ArtifactStore(root=str(tmp_path), io_workers=4, **CHUNK_KW)
    art, w = big_artifact()
    rng = np.random.default_rng(1)

    def child(base):   # every chunk changes, every delta compresses
        v = base + rng.normal(0, 1e-3, base.shape).astype(np.float32)
        return ModelArtifact(art.graph, {"big/w": v}), v

    r1 = store.commit_artifact("m1", art)
    art2, w2 = child(w)
    r2 = store.commit_artifact("m2", art2, parent_ref=r1)
    art3, _ = child(w2)
    store = ArtifactStore(root=str(tmp_path), io_workers=4, **CHUNK_KW)
    with tracing():
        r3 = store.commit_artifact("m3", art3, parent_ref=r2)
    commit_evs = _span_events()
    items = store.get_manifest(r3)["params"]["big/w"]["chunks"]
    n = len(items)
    assert n > 2 and all("b" in it for it in items)
    assert all(e["args"]["n"] > 0 for e in commit_evs
               if e["name"].startswith(("chunk.", "commit.d2h")))
    assert {name: _count(commit_evs, name) for name in (
        "commit.d2h", "chunk.hash", "chunk.parent", "chunk.read",
        "chunk.decode", "chunk.quantize", "chunk.encode",
        "chunk.write")} == {
        "commit.d2h": 1, "chunk.hash": 2 * n, "chunk.parent": n,
        "chunk.read": n, "chunk.decode": n, "chunk.quantize": 2 * n,
        "chunk.encode": n, "chunk.write": n}
    assert set(_roots(commit_evs).values()) == {"store.commit"}

    reset_trace()
    store = ArtifactStore(root=str(tmp_path), io_workers=4, **CHUNK_KW)
    with tracing():
        got = store.materialize_artifact(r3).params["big/w"]
    evs = _span_events()
    assert _count(evs, "chunk.read") == n and _count(evs, "chunk.decode") == n
    assert set(_roots(evs).values()) == {"store.checkout"}

    reset_trace()
    store = ArtifactStore(root=str(tmp_path), io_workers=4, **CHUNK_KW)
    with tracing():
        direct = store.materialize_param(r3, "big/w")
    evs = _span_events()
    chunk_evs = [e for e in evs if e["name"].startswith("chunk.")]
    assert len(chunk_evs) == 2 * n
    # the fan-out ran on the store's pool and on the caller, which runs the
    # chunks no worker has started; propagate() kept the parent on each
    pool = {t.ident for t in threading.enumerate()
            if t.name.startswith("artifact-store-io")}
    assert pool
    assert {e["tid"] for e in chunk_evs} <= pool | {threading.get_ident()}
    assert set(_roots(evs).values()) == {"checkout.param"}
    assert np.array_equal(got.view(np.uint32), direct.view(np.uint32))


@pytest.mark.parametrize("on_device", [False, True])
def test_commit_d2h_spans_carry_view(tmp_path, monkeypatch, on_device):
    """Every ``commit.d2h`` span names how its leaf came to the host: a
    device leaf as uint32 words (``u32``), in the chunk layer and in the
    whole-tensor delta workers alike; a NumPy leaf as it is (``asis``)."""
    import jax.numpy as jnp
    from repro.core import LayerGraph, LayerNode
    from repro.kernels import ops
    from test_chunks import CHUNK_KW

    monkeypatch.setattr(ops, "_tiled", lambda a: True)  # as on a TPU
    shapes = {"big/w": (256, 300), "small/w": (24, 40)}
    graph = LayerGraph.chain([LayerNode(k.split("/")[0], "linear",
                                        params={"w": (s, "bfloat16")})
                              for k, s in shapes.items()])
    rng = np.random.default_rng(0)
    base = {k: rng.standard_normal(s).astype("bfloat16")
            for k, s in shapes.items()}
    child = {k: (v + rng.standard_normal(v.shape) * 0.1).astype(v.dtype)
             for k, v in base.items()}
    if on_device:
        child = {k: jnp.asarray(v) for k, v in child.items()}
    store = ArtifactStore(root=str(tmp_path), io_workers=4, **CHUNK_KW)
    r1 = store.commit_artifact("m1", ModelArtifact(graph, base))
    with tracing():
        store.commit_artifact("m2", ModelArtifact(graph, child),
                              parent_ref=r1)
    d2h = {e["args"]["key"]: e["args"]["view"] for e in _span_events()
           if e["name"] == "commit.d2h"}
    view = "u32" if on_device else "asis"
    assert d2h == {"big/w": view, "small/w": view}


def _parent_names(evs):
    by_id = {e["args"]["span_id"]: e for e in evs}
    return {e["args"]["span_id"]: by_id[e["args"]["parent_id"]]["name"]
            for e in evs if e["args"]["parent_id"] in by_id}


def test_lossless_chunk_hop_spans_and_counters(tmp_path):
    """A bf16 chunked commit against its parent opens one ``chunk.xdelta``
    per changed chunk under the commit's ``commit.chunk_stream``, whose
    args count them, as ``io_stats`` does with the bytes they wrote; the
    checkout decodes each inside ``chunk.decode``, marked as such."""
    from test_chunks import CHUNK_KW, _ulp_finetune, big_artifact

    store = ArtifactStore(root=str(tmp_path), io_workers=4, **CHUNK_KW)
    art, w = big_artifact(dtype="bfloat16")
    r1 = store.commit_artifact("m1", art)
    child = ModelArtifact(art.graph, {"big/w": _ulp_finetune(w, 1)})
    store.reset_io_stats()
    with tracing():
        r2 = store.commit_artifact("m2", child, parent_ref=r1)
    evs = _span_events()
    items = store.get_manifest(r2)["params"]["big/w"]["chunks"]
    n = len(items)
    assert n > 2 and all(it.get("x") for it in items)
    xd = [e for e in evs if e["name"] == "chunk.xdelta"]
    assert len(xd) == n and all(e["args"]["n"] > 0 for e in xd)
    parents = _parent_names(evs)
    assert {parents[e["args"]["span_id"]] for e in xd} == {
        "commit.chunk_stream"}
    (stream,) = [e for e in evs if e["name"] == "commit.chunk_stream"]
    assert stream["args"]["xdelta_chunks"] == n
    assert _count(evs, "chunk.quantize") == _count(evs, "chunk.encode") == 0
    assert store.io_stats["chunk_xdelta_blobs"] == n
    written = sum(store.cas.size(it["b"]) for it in items)
    assert store.io_stats["chunk_xdelta_bytes"] == written > 0

    reset_trace()
    store = ArtifactStore(root=str(tmp_path), io_workers=4, **CHUNK_KW)
    with tracing():
        store.materialize_artifact(r2)
    dec = [e for e in _span_events() if e["name"] == "chunk.decode"]
    assert len(dec) == n
    assert {e["args"]["kind"] for e in dec} == {"xdelta"}


def test_checkpoint_save_spans_transfer_and_wait(tmp_path):
    import jax.numpy as jnp
    from repro.store.checkpoint import CheckpointManager

    cm = CheckpointManager(str(tmp_path), model_name="m", async_save=True)
    state = {"w": jnp.arange(64 * 1024, dtype=jnp.float32),
             "b": jnp.ones((8,), jnp.float32)}
    with tracing():
        cm.save(1, state)
        cm.wait()
    cm.close()
    evs = _span_events()
    by_id = {e["args"]["span_id"]: e for e in evs}
    transfers = [e for e in evs if e["name"] == "ckpt.transfer"]
    assert sorted(e["args"]["key"] for e in transfers) == ["b", "w"]
    assert {e["args"]["n"] for e in transfers} == {32, 256 * 1024}
    assert all(by_id[e["args"]["parent_id"]]["name"] == "ckpt.snapshot"
               for e in transfers)
    assert _count(evs, "ckpt.wait") == 1


# ---------------------------------------------------------------------------
# Daemon exposition: /api/metrics and per-route latency
# ---------------------------------------------------------------------------

def _get_text(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.headers.get("Content-Type", ""), r.read().decode()


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def _post_json(url, obj):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


def test_hub_api_metrics_and_latency(tmp_path):
    g = _repo(tmp_path / "src")
    _seed(g)
    app = HubApp(str(tmp_path / "hub"))
    server, _ = hub_start(app)
    try:
        push(g, HttpTransport(server.url, retries=0),
             state=RemoteState(g.path, "origin"))
        ctype, text = _get_text(server.url + "/api/metrics")
        assert ctype.startswith("text/plain")
        samples, types = _parse_prometheus(text)
        assert types.get("mgit_http_request_seconds") == "histogram"
        inst = app.stats.instance
        assert samples[f'mgit_hub_requests{{instance="{inst}"}}'] > 0
        served = sum(v for k, v in samples.items()
                     if k.startswith("mgit_http_request_seconds_count")
                     and f'service="hub"' in k and f'instance="{inst}"' in k)
        assert served > 0
        # journal writes land under the :tid route family, not raw paths
        assert any('route="/api/journal/:tid"' in k for k in samples)
        stats = _get_json(server.url + "/api/stats")
        lat = stats["request_latency"]
        key = next(k for k in lat if "/api/journal/:tid" in k)
        assert lat[key]["count"] > 0 and lat[key]["p99_ms"] >= 0
    finally:
        server.shutdown()
        server.server_close()


def test_serve_api_metrics_and_latency(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    g = LineageGraph(path=str(tmp_path), store=store)
    base = make_chain_model(seed=0)
    g.add_node(base, "main")
    g.add_edge("main", "canary")
    g.add_node(perturb(base, "L0/w", seed=3), "canary")
    router = Router(ModelPool(store), ["prod=branch:main"])
    watcher = LineageWatcher(LocalLineageSource(str(tmp_path)), router,
                             interval_s=30)
    watcher.poll()
    app = ServeApp(router, router.pool, watcher)
    server, _ = serve_start(app)
    try:
        for _ in range(3):
            _post_json(server.url + "/api/predict/prod", {})
        ctype, text = _get_text(server.url + "/api/metrics")
        assert ctype.startswith("text/plain")
        samples, types = _parse_prometheus(text)
        inst = app.counters.instance
        assert samples[f'mgit_serve_predictions{{instance="{inst}"}}'] == 3
        key = ('mgit_http_request_seconds_count{instance="%s",'
               'method="POST",route="/api/predict/:endpoint",'
               'service="serve"}' % inst)
        assert samples[key] == 3
        lat = _get_json(server.url + "/api/stats")["request_latency"]
        assert lat["POST /api/predict/:endpoint"]["count"] == 3
        assert lat["POST /api/predict/:endpoint"]["p50_ms"] >= 0
    finally:
        server.shutdown()
        server.server_close()


def test_unknown_paths_collapse_to_other_route_label(tmp_path):
    app = HubApp(str(tmp_path / "hub"))
    server, _ = hub_start(app)
    try:
        for i in range(3):  # distinct junk paths -> ONE label value
            with pytest.raises(urllib.error.HTTPError):
                _get_json(server.url + f"/api/junk{i}")
        samples, _ = _parse_prometheus(
            _get_text(server.url + "/api/metrics")[1])
        junk = [k for k in samples if "junk" in k]
        assert not junk  # cardinality stays bounded
        inst = app.stats.instance
        key = ('mgit_http_request_seconds_count{instance="%s",'
               'method="GET",route="other",service="hub"}' % inst)
        assert samples[key] == 3
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# Satellite: watcher failure visibility
# ---------------------------------------------------------------------------

class _FlakySource:
    def __init__(self, fail_times, name):
        self.fail_times = fail_times
        self.name = name  # unique: the registry child is keyed on describe()
        self.calls = 0

    def fetch(self):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise ConnectionError(f"flake #{self.calls}")
        return None, "absent"

    def describe(self):
        return f"flaky:{self.name}"


def test_watcher_counts_failures_and_recovers(tmp_path, caplog):
    store = ArtifactStore(root=str(tmp_path))
    router = Router(ModelPool(store), ["prod=ref:nothing"])
    src = _FlakySource(fail_times=2, name="poll-test")
    w = LineageWatcher(src, router, interval_s=0.01)
    with caplog.at_level("WARNING", logger="repro.serve.watch"):
        for _ in range(2):
            try:
                w.poll()
            except ConnectionError as exc:
                w._record_failure(exc)
    assert w.consecutive_failures == 2
    assert "flake #1" in w.last_error or "flake #2" in w.last_error
    # one WARN per outage, not one per tick
    warns = [r for r in caplog.records if "lineage watch poll" in r.message]
    assert len(warns) == 1
    w.poll()  # source recovered
    st = w.stats()
    assert st["consecutive_failures"] == 0 and st["last_error"] is None
    assert st["poll_failures"] == 2


def test_watcher_run_loop_survives_failures(tmp_path):
    store = ArtifactStore(root=str(tmp_path))
    router = Router(ModelPool(store), ["prod=ref:nothing"])
    src = _FlakySource(fail_times=3, name="run-loop-test")
    w = LineageWatcher(src, router, interval_s=0.005)
    w.start()
    try:
        deadline = threading.Event()
        for _ in range(400):
            if src.calls > 4:
                break
            deadline.wait(0.01)
        assert src.calls > 4  # kept polling straight through the failures
    finally:
        w.stop()
    assert w.stats()["poll_failures"] == 3


# ---------------------------------------------------------------------------
# Satellite: transport retries visible per endpoint family
# ---------------------------------------------------------------------------

def test_endpoint_family_mapping():
    assert endpoint_family("/api/objects/abc123") == "objects"
    assert endpoint_family("/api/journal/t1") == "journal"
    assert endpoint_family("/api/lineage") == "lineage"
    assert endpoint_family("/api/have") == "negotiate"
    assert endpoint_family("/api/finalize") == "finalize"
    assert endpoint_family("/api/ping") == "ping"
    assert endpoint_family("/api/whatever") == "other"


def test_http_retries_are_counted_per_family():
    t = HttpTransport("http://127.0.0.1:9", retries=1, backoff=0.001)
    with pytest.raises(HubUnavailable):
        t.have(["k"])
    st = t.retry_stats()
    assert st["retries"] == {"negotiate": 1}
    assert st["terminal_failures"] == {"negotiate": 1}
    assert st["backoff_s"]["negotiate"] > 0


def test_push_report_surfaces_transport_retries(tmp_path):
    g = _repo(tmp_path / "src")
    _seed(g)
    t = HttpTransport("http://127.0.0.1:9", retries=1, backoff=0.001)
    with pytest.raises(HubUnavailable):
        push(g, t, state=RemoteState(g.path, "o"))
    # pre-seed noise, then a live push: the report counts ONLY its own sync
    app = HubApp(str(tmp_path / "hub"))
    server, _ = hub_start(app)
    try:
        t2 = HttpTransport(server.url, retries=1, backoff=0.001)
        rep = push(g, t2, state=RemoteState(g.path, "o"))
        assert rep.published
        assert rep.transport_retries == 0
        assert rep.transport_retries_by_family == {}
        assert rep.transport_terminal_failures == 0
    finally:
        server.shutdown()
        server.server_close()
    assert rep.to_json()["transport_retries"] == 0


# ---------------------------------------------------------------------------
# CLI: obs metrics / obs trace
# ---------------------------------------------------------------------------

def test_cli_obs_metrics(tmp_path, capsys):
    g = _repo(tmp_path)
    _seed(g)
    assert cli(["-C", str(tmp_path), "obs", "metrics"]) == 0
    samples, types = _parse_prometheus(capsys.readouterr().out)
    assert any(k.startswith("mgit_store_") for k in samples)


def test_cli_obs_trace_emits_perfetto_json(tmp_path, capsys):
    g = _repo(tmp_path)
    _seed(g)
    out = str(tmp_path / "trace.json")
    assert cli(["-C", str(tmp_path), "obs", "--out", out, "trace",
                "checkout", "m@v2"]) == 0
    capsys.readouterr()
    doc = json.load(open(out))
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in evs}
    assert {"store.checkout", "checkout.param"} <= names
    assert not is_enabled()  # tracing restored off after the run
